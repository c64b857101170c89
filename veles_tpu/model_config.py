"""The model-config record: what KIND of decoder-only language model a
param tree is (ROADMAP D2).

One frozen, hashable value that ``ops/transformer.py``, ``ops/attention.py``,
``TransformerTrainer``, ``restful_api.serve_lm`` and ``serving.LMEngine``
read in place of the keyword arguments ``n_heads, rope, window, sinks`` and
of dictionary keys looked up in the tree: the sizes the tree cannot state
(``head_dim`` need not be ``d_model // n_heads``), the block's wiring, the
kind of each layer's attention and feed forward, and the dtype with its
accumulation rule (ROADMAP D8: ``functional._PRECISION`` stays the policy of
float32 models only).

Two blocks:

- ``pre_ln`` — the block the repo always had: LayerNorm with bias before
  attention and before a ReLU feed forward (or the top-1 expert layer of a
  tree that carries ``moe``), learned or rotary positions for the whole
  stack, one window for the whole stack, tied head.  :func:`classic` makes
  its record from the old keyword arguments, and every function that took
  those still does.
- ``sandwich`` — RMSNorm without bias before AND after attention and feed
  forward, per-head q/k RMSNorm, rotary positions on sliding-window layers
  and none on full layers, a sigmoid output gate on the heads' outputs,
  gated-SiLU feed forward (dense, or sigmoid-routed experts beside a shared
  expert), embedding scaled by sqrt(d_model), untied head.

- ``pre_rms`` — RMSNorm without bias before attention and before a
  gated-SiLU feed forward (dense in the leading layers, then sigmoid-routed
  experts beside a shared expert), untied head, and LATENT attention
  (``latent``): queries through a low-rank bottleneck, keys and values
  re-expanded from one cached latent row a token (``kv_rank`` numbers and
  ``rope`` rotated ones shared by all heads), rotary positions scaled by
  YaRN (``yarn``).  Under ``hyper`` the residual is ``streams`` parallel
  streams mixed around every sublayer by input-dependent coefficients
  (manifold-constrained hyper-connections): see ``ops/hyper.py``.

  With ``linear`` the same block runs over layers of two kinds
  (``attn_kinds``): ``linear`` layers mix tokens through a gated delta rule
  (``linear``, ``ops/linear_attn.py``) and keep a recurrent state and a
  convolution tail a sequence, whatever its length; ``full`` layers are
  the latent attention above where the record has ``latent`` (one pool of
  latent rows a ``full`` layer beside the linear layers' state), else
  grouped-query softmax attention whose sigmoid output gate is the second
  half of ``wq``'s output, with per-head q/k norms and rotary positions on
  the first ``partial_rotary`` of a head.  ``norm_centred``: every RMSNorm
  gain is stored about zero (``1 + w``).

  The ``linear`` layers' rule may be the state-space one
  (``LinearConfig.rule == "ssd"``: Mamba-2, the same slots under a rule
  without the delta correction); beside them the ``full`` layers may be
  PLAIN grouped-query attention (``plain_full``) without any position
  signal (``rope`` false), every layer's feed forward dense, and the stack
  may carry Granite's four multipliers and a tied head (``embed_mult``,
  ``residual_mult``, ``attn_scale``, ``logits_div``, ``tied``).

A record with ``attn_kinds`` naming both ``sliding`` and ``full`` layers has
two kinds of KV cache (``kinds``): the engine keeps a page table and an
allocator for each.  A latent record has one kind (``full``): one table,
and ONE pool array a layer whose rows are the latents.  ``linear`` layers
hold no pages (``kinds`` leaves them out): the engine keeps a slot of state
a lane for each beside the full layers' one table (``state_layers``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

SLIDING, FULL, LINEAR = "sliding", "full", "linear"
DENSE, MOE = "dense", "moe"


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """The routed feed forward of one model: ``router_width`` experts are
    scored, ``top_k`` chosen per token, and ``held = (lo, n)`` says which
    of them this param tree carries (an expert-parallel share; the others'
    part of the sum is another chip's).  With ``n_group`` > 1 the choice is
    group-limited (``ops/moe.py::route``): the experts lie in ``n_group``
    equal groups in order, and a token chooses among the experts of its
    ``topk_group`` best groups."""
    router_width: int
    top_k: int = 1
    score: str = "softmax"            # | "sigmoid"
    route_norm: bool = False          # weights / their sum over the chosen
    route_scale: float = 1.0
    held: Optional[Tuple[int, int]] = None    # None: all of them
    shared: bool = False              # a shared expert beside the routed
    #: the shared expert's output times ``sigmoid(x w_gate)``, one per token
    shared_gate: bool = False
    n_group: int = 1
    topk_group: int = 1

    def __post_init__(self):
        if self.n_group < 1 or self.router_width % self.n_group \
                or not 1 <= self.topk_group <= self.n_group:
            raise ValueError("n_group %d must divide router_width %d, and "
                             "topk_group %d lie in 1..n_group"
                             % (self.n_group, self.router_width,
                                self.topk_group))
        if self.n_group > 1 and (
                self.router_width // self.n_group < 2
                or self.topk_group * (self.router_width // self.n_group)
                < self.top_k):
            raise ValueError("group-limited routing scores a group by its "
                             "two best experts and needs top_k experts in "
                             "the groups kept")

    def held_range(self):
        return self.held if self.held is not None else (0, self.router_width)


@dataclasses.dataclass(frozen=True)
class LatentConfig:
    """Latent attention's five sizes: the queries' bottleneck (None: no
    bottleneck, one matrix ``wq``), the cached latent, and per head the
    unrotated and rotated parts of a query or key and a value's width.
    ``head_gate``: each head's output times ``sigmoid(x w_gate)_h`` before
    ``W_o`` (``w_gate``: (d, heads))."""
    q_rank: Optional[int]
    kv_rank: int
    nope: int
    rope: int
    v: int
    head_gate: bool = False

    @property
    def width(self):
        """Numbers a cached token holds a layer: the latent and the one
        rotated key all heads share."""
        return self.kv_rank + self.rope

    @property
    def row(self):
        """Lanes of one pool row: ``width`` rounded up to the chip's 128
        (576 -> 640).  The chip's tiled layout pads an array's minor axis
        to 128 lanes whatever is asked for, so the padding costs no byte
        that a 576-wide array would not; stating it keeps the pool the
        shape the kernels read and no dispatch converts it."""
        return -(-self.width // 128) * 128


@dataclasses.dataclass(frozen=True)
class LinearConfig:
    """A gated-delta-rule (linear attention) layer: ``k_heads`` query/key
    heads of ``k_dim``, ``v_heads`` value heads of ``v_dim`` (a multiple of
    the key heads: each key head serves ``v_heads / k_heads`` value heads),
    a causal depthwise convolution of ``conv`` positions over ``[q, k,
    v]``.  ``decay``: the state decays by one factor a ``head`` each token
    (Gated DeltaNet: ``g = -exp(A_log) softplus(a + dt_bias)``) or by one a
    key ``channel`` (Kimi Delta Attention: ``g = lower_bound sigmoid(
    exp(A_log) (x W_f + dt_bias))``, in ``(lower_bound, 0)``); ``gate``: the
    output gate is ``silu`` or ``sigmoid`` of ``x W_z``.

    ``rule == "ssd"``: a state-space layer (Mamba-2, arXiv:2405.21060) in
    the same slots: ``k_heads`` GROUPS whose one ``B`` (the key) and one
    ``C`` (the query) of ``k_dim`` (the state size) serve ``v_heads /
    k_heads`` heads of ``v_dim`` each, the convolution over ``[x | B | C]``
    WITH a bias, ``dt = softplus(x W_dt + dt_bias)`` in ``beta``'s place and
    ``g = -exp(A_log) dt``; the rule has NO delta correction (``S <-
    exp(g) S + B (dt x)^T``; ``y = S^T C + D x``), no L2 norm and no scale
    on ``C``, and the output is ``(rms(y * silu(z)) * w_n) W_o`` with the
    norm over the WHOLE inner width, after the gate."""
    k_heads: int
    v_heads: int
    k_dim: int
    v_dim: int
    conv: int = 4
    decay: str = "head"               # | "channel"
    gate: str = "silu"                # | "sigmoid"
    #: the least log decay a token (``channel`` only): the chunked rule's
    #: exponents about a block's first row stay under 16 x this
    lower_bound: Optional[float] = None
    rule: str = "delta"               # | "ssd"

    def __post_init__(self):
        if self.decay not in ("head", "channel") \
                or self.gate not in ("silu", "sigmoid"):
            raise ValueError("unknown decay %r or gate %r"
                             % (self.decay, self.gate))
        if self.rule not in ("delta", "ssd") or (
                self.rule == "ssd" and (self.decay, self.gate)
                != ("head", "silu")):
            raise ValueError("unknown rule %r (the state-space rule decays "
                             "by head under a silu gate)" % (self.rule,))
        if self.v_heads % self.k_heads:
            raise ValueError("k_heads %d must divide v_heads %d"
                             % (self.k_heads, self.v_heads))
        if (self.decay == "channel") != (self.lower_bound is not None):
            raise ValueError("a decay per channel and its lower_bound come "
                             "together")
        if self.lower_bound is not None \
                and not -5.0 <= self.lower_bound < 0.0:
            raise ValueError(
                "lower_bound %r: the chunked rule holds 16 rows of decay in "
                "one float32 exponent (16 x 5 = 80 < 88)"
                % (self.lower_bound,))

    @property
    def key_width(self):
        return self.k_heads * self.k_dim

    @property
    def value_width(self):
        return self.v_heads * self.v_dim

    @property
    def conv_width(self):
        """Channels the convolution runs over: ``[q | k | v]`` (``[x | B |
        C]`` of a state-space layer: the same sum)."""
        return 2 * self.key_width + self.value_width

    @property
    def pack(self):
        """Value heads that lie side by side in one row of the stored state
        (a state-space layer's only): as many heads of one group as fill the
        chip's 128 lanes (2 of 64).  The chip pads an array's minor axis to
        128 lanes whatever is asked for, so a state of (.., k_dim, 64) would
        hold and move twice its bytes; the heads of a group share ``B`` and
        ``C``, so a row of several heads' values is updated by one outer
        product and read by one sum, as a head of the wider value would
        be."""
        if self.rule != "ssd":
            return 1
        r = max(1, 128 // self.v_dim)
        while (self.v_heads // self.k_heads) % r:
            r -= 1
        return r

    def state_shapes(self, slots):
        """(recurrent state, convolution tail) of ``slots`` sequences: ``S``
        (slots, v_heads / pack, k_dim, pack x v_dim), always float32, and
        the last ``conv - 1`` pre-convolution rows (slots, conv - 1,
        conv_width) in the model's dtype."""
        r = self.pack
        return ((slots, self.v_heads // r, self.k_dim, r * self.v_dim),
                (slots, self.conv - 1, self.conv_width))


@dataclasses.dataclass(frozen=True)
class YarnConfig:
    """YaRN's scaling of the rotary frequencies (``rope_scaling`` of type
    ``yarn``)."""
    factor: float
    original: int                     # original_max_position_embeddings
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class HyperConfig:
    """The residual of ``streams`` streams: Sinkhorn iterations and their
    epsilon, and the clamp on the mixing matrix's logits."""
    streams: int
    iters: int = 20
    eps: float = 1e-6
    clamp: Tuple[float, float] = (-30.0, 30.0)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    n_heads: int
    block: str = "pre_ln"             # | "sandwich" | "pre_rms"
    #: None: read off ``wk``'s width (the classic tree states it)
    n_kv_heads: Optional[int] = None
    #: None: ``d_model // n_heads``
    head_dim: Optional[int] = None
    rope: bool = False
    rope_theta: float = 10000.0
    window: Optional[int] = None
    sinks: int = 0
    #: per layer ``sliding`` | ``full``; None: every layer alike (``rope``
    #: and ``window`` above hold for the whole stack)
    attn_kinds: Optional[Tuple[str, ...]] = None
    #: per layer ``dense`` | ``moe``; None: read off the tree (``moe`` key)
    ffn_kinds: Optional[Tuple[str, ...]] = None
    moe: Optional[MoEConfig] = None
    #: weights, activations and KV storage; norm statistics, softmax,
    #: matmul accumulation, router scores and the logits are float32
    dtype: str = "float32"
    eps: float = 1e-5
    #: latent attention (``pre_rms`` only); None: heads project their own
    #: keys and values
    latent: Optional[LatentConfig] = None
    yarn: Optional[YarnConfig] = None
    #: the n-stream residual (``pre_rms`` only); None: one plain stream
    hyper: Optional[HyperConfig] = None
    #: the ``linear`` layers' sizes (``pre_rms``); beside ``latent`` the
    #: ``full`` layers are the latent ones
    linear: Optional[LinearConfig] = None
    #: share of a head's dimensions, from the first, that rotary positions
    #: rotate (``pre_rms`` without ``latent``)
    partial_rotary: float = 1.0
    #: RMSNorm gains are stored about zero: the norm multiplies by ``1 + w``
    norm_centred: bool = False
    #: multi-token-prediction modules the tree carries under ``"mtp"``
    #: (``pre_rms`` with ``latent``): each one more expert layer behind the
    #: stack, which drafts the token after next from the last block's
    #: output and the next token's embedding (``ops/transformer.py::
    #: mtp_forward``); ``ffn_kinds`` names its layer behind the stack's
    nextn: int = 0
    #: Granite's four multipliers (``pre_rms`` over one stream): the token
    #: rows times ``embed_mult``; every sublayer's output times
    #: ``residual_mult`` as it joins the stream; the softmax scale of the
    #: ``full`` layers in ``head_dim ** -0.5``'s place; the logits divided
    #: by ``logits_div``
    embed_mult: Optional[float] = None
    residual_mult: float = 1.0
    attn_scale: Optional[float] = None
    logits_div: float = 1.0
    #: the head is the embedding's transpose: the tree carries no ``head``
    tied: bool = False
    #: the ``full`` layers beside linear ones are PLAIN grouped-query
    #: attention: no output gate in ``wq``, no per-head norms
    plain_full: bool = False

    def __post_init__(self):
        if self.block not in ("pre_ln", "sandwich", "pre_rms"):
            raise ValueError("unknown block %r" % (self.block,))
        if self.block != "pre_rms" and (
                self.latent or self.yarn or self.hyper or self.linear):
            raise ValueError("latent, yarn, hyper and linear belong to the "
                             "pre_rms block")
        if self.block == "pre_rms" and self.latent is None \
                and self.attn_kinds is None:
            raise ValueError("the pre_rms block is latent attention, or "
                             "names its layers' kinds (attn_kinds)")
        if self.latent is not None and self.attn_kinds is not None \
                and self.linear is None:
            raise ValueError("a latent stack names its layers' kinds only "
                             "beside linear layers")
        if (self.embed_mult is not None or self.residual_mult != 1.0
                or self.attn_scale is not None or self.logits_div != 1.0
                or self.tied or self.plain_full) and (
                    self.block != "pre_rms" or self.latent is not None
                    or self.hyper is not None):
            raise ValueError("the multipliers, the tied head and plain full "
                             "layers belong to the pre_rms block over one "
                             "stream, without latent attention")
        if self.nextn not in (0, 1) or (self.nextn and (
                self.latent is None or self.hyper is not None
                or self.linear is not None)):
            raise ValueError("one multi-token-prediction module at most, "
                             "behind a latent stack of one residual stream")
        if self.attn_kinds is not None:
            bad = set(self.attn_kinds) - {SLIDING, FULL, LINEAR}
            if bad:
                raise ValueError("unknown attention kind(s) %r" % (bad,))
            if (LINEAR in self.attn_kinds) != (self.linear is not None):
                raise ValueError("linear layers and their sizes (linear=) "
                                 "come together")
            if self.linear is not None and SLIDING in self.attn_kinds:
                raise ValueError("linear layers stand beside full layers "
                                 "only")
            if SLIDING in self.attn_kinds and not self.window:
                raise ValueError("sliding layers need window=W")
            if self.sinks:
                raise ValueError("attention sinks are not defined for "
                                 "per-layer attention kinds")

    # ------------------------------------------------------------- layers
    @property
    def kinds(self):
        """The kinds of KV cache the stack needs, in a fixed order:
        ``("full",)`` for a stack whose layers are alike, else the kinds
        that ``attn_kinds`` names."""
        if self.attn_kinds is None:
            return (FULL,)
        return tuple(k for k in (FULL, SLIDING) if k in self.attn_kinds)

    @property
    def state_layers(self):
        """The layers that hold a recurrent state and no pages."""
        if self.linear is None:
            return ()
        return tuple(i for i, k in enumerate(self.attn_kinds)
                     if k == LINEAR)

    @property
    def by_kind(self):
        """The stack names its layers' attention kinds."""
        return self.attn_kinds is not None

    def kind(self, layer):
        """Which cache kind layer ``layer`` reads and writes (``linear``:
        a state slot, no pages)."""
        if self.attn_kinds is None:
            return FULL
        return self.attn_kinds[layer]

    def layer_rope(self, layer):
        if self.attn_kinds is None:
            return self.rope
        if self.block == "pre_rms":
            return self.rope and self.attn_kinds[layer] == FULL
        return self.attn_kinds[layer] == SLIDING

    def rotary_dims(self, head_dim):
        """How many of a head's dimensions, from the first, are rotated."""
        return int(head_dim * self.partial_rotary)

    def layer_window(self, layer):
        if self.attn_kinds is None:
            return self.window
        return self.window if self.attn_kinds[layer] == SLIDING else None

    def ffn_kind(self, layer, blk=None):
        if self.ffn_kinds is not None:
            return self.ffn_kinds[layer]
        return MOE if blk is not None and "moe" in blk else DENSE

    def window_pages(self, page):
        """Pages a lane holds at most in a sliding layer's pool: the
        window, the page the frontier is in, and one taken before the
        oldest is released."""
        return -(-self.window // page) + 2

    # -------------------------------------------------------------- sizes
    def head_size(self, d_model):
        return self.head_dim or d_model // self.n_heads

    def kv_heads(self, attn_params, d_model):
        if self.n_kv_heads is not None:
            return self.n_kv_heads
        return attn_params["wk"].shape[-1] // self.head_size(d_model)

    def embed_scale(self, d_model):
        return math.sqrt(d_model) if self.block == "sandwich" else None

    def query_scale(self, head_dim):
        """What a ``full`` layer's queries are multiplied by BEFORE the
        attention (whose softmax scale stays ``head_dim ** -0.5``, in the
        kernels too): 1, or ``attn_scale x sqrt(head_dim)``, so that the
        scores come out times ``attn_scale`` (1/8 for 1/64 at a head of 64:
        a power of two, exact in any dtype)."""
        if self.attn_scale is None:
            return 1.0
        return self.attn_scale * math.sqrt(head_dim)

    @property
    def wide(self):
        """The residual stream is float32 whatever the model's dtype."""
        return self.block in ("sandwich", "pre_rms")

    @property
    def streams(self):
        return self.hyper.streams if self.hyper is not None else 1


def classic(n_heads, rope=False, window=None, sinks=0):
    """The record of the repo's own block from the keyword arguments its
    functions have always taken."""
    return ModelConfig(n_heads=int(n_heads), rope=bool(rope),
                       window=window or None, sinks=int(sinks or 0))


def of(cfg_or_heads, rope=False, window=None, sinks=0):
    """``cfg_or_heads`` as a record: a :class:`ModelConfig` is returned as
    it is (and must not be given the classic keywords beside it), a head
    count makes the classic one."""
    if isinstance(cfg_or_heads, ModelConfig):
        if rope or window or sinks:
            raise ValueError("rope/window/sinks belong in the ModelConfig "
                             "record, not beside it")
        return cfg_or_heads
    return classic(cfg_or_heads, rope, window, sinks)


def from_published(cfg):
    """The record of a published ``config.json`` (a dict under its own
    keys), by ``model_type``.  ``granitemoehybrid`` is read where it has no
    routed experts (Granite 4.0-H Micro).  ``afmoe``, ``qwen3_next``,
    ``joyai_llm_flash`` and ``ling3_flash`` also read two keys of a
    deployment's share where they are given: ``held_experts`` ``[lo, n]``
    (this tree's experts, of ``router_width`` that the router scores)."""
    family = cfg.get("model_type")
    if family == "granitemoehybrid":
        return _granite_hybrid(cfg)
    if family == "ling3_flash":
        return _ling3(cfg)
    if family == "xing4_0":
        return _xing4(cfg)
    if family == "joyai_llm_flash":
        return _joyai(cfg)
    if family == "qwen3_next":
        return _qwen3_next(cfg)
    if family != "afmoe":
        raise ValueError("no record for model_type %r (the pre_ln block "
                         "is made by model_config.classic)" % (family,))
    kinds = tuple(SLIDING if k == "sliding_attention" else FULL
                  for k in cfg["layer_types"])
    if len(kinds) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types names %d layers of %d"
                         % (len(kinds), cfg["num_hidden_layers"]))
    width = cfg.get("router_width", cfg["num_experts"])
    held = tuple(cfg.get("held_experts") or (0, cfg["num_experts"]))
    return ModelConfig(
        n_heads=cfg["num_attention_heads"], block="sandwich",
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_theta"]), window=cfg["sliding_window"],
        attn_kinds=kinds,
        ffn_kinds=tuple(DENSE if i < cfg["num_dense_layers"] else MOE
                        for i in range(len(kinds))),
        moe=MoEConfig(router_width=width,
                      top_k=cfg["num_experts_per_tok"],
                      score=cfg["score_func"],
                      route_norm=bool(cfg["route_norm"]),
                      route_scale=float(cfg["route_scale"]), held=held,
                      shared=cfg["num_shared_experts"] > 0),
        dtype=cfg.get("dtype", "bfloat16"), eps=cfg["rms_norm_eps"])


def _xing4(cfg):
    """``model_type: xing4_0``: latent attention under YaRN, leading dense
    layers then sigmoid-routed experts (``noaux_tc``: a selection bias,
    the choice limited to ``topk_group`` of ``n_group`` groups) beside
    shared ones, an ``hc_mult``-stream residual.  The
    multi-token-prediction module is not part of the record: a tree that
    carries one is served without it."""
    if cfg.get("topk_method", "noaux_tc") != "noaux_tc":
        raise ValueError("xing4_0: only noaux_tc selection")
    if cfg.get("n_shared_experts", 0) > 1:
        raise ValueError("xing4_0: one shared expert or none")
    scaling = cfg.get("rope_scaling") or None
    if scaling is not None and scaling.get("type") != "yarn":
        raise ValueError("xing4_0: rope_scaling of type %r"
                         % (scaling.get("type"),))
    n = cfg["num_hidden_layers"]
    return ModelConfig(
        n_heads=cfg["num_attention_heads"], block="pre_rms",
        rope=True, rope_theta=float(cfg["rope_theta"]),
        latent=LatentConfig(
            q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
            nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
            v=cfg["v_head_dim"]),
        yarn=None if scaling is None else YarnConfig(
            factor=float(scaling["factor"]),
            original=int(scaling["original_max_position_embeddings"]),
            beta_fast=float(scaling.get("beta_fast", 32)),
            beta_slow=float(scaling.get("beta_slow", 1)),
            mscale=float(scaling.get("mscale", 1)),
            mscale_all_dim=float(scaling.get("mscale_all_dim", 0))),
        hyper=None if "hc_mult" not in cfg else HyperConfig(
            streams=int(cfg["hc_mult"]),
            iters=int(cfg.get("hc_sinkhorn_iters", 20)),
            eps=float(cfg.get("hc_eps", 1e-6)),
            clamp=(float(cfg.get("mhc_h_res_clamp_min", -30)),
                   float(cfg.get("mhc_h_res_clamp_max", 30)))),
        ffn_kinds=tuple(DENSE if i < cfg["first_k_dense_replace"] else MOE
                        for i in range(n)),
        moe=MoEConfig(router_width=cfg["n_routed_experts"],
                      top_k=cfg["num_experts_per_tok"],
                      score=cfg["scoring_func"],
                      route_norm=bool(cfg["norm_topk_prob"]),
                      route_scale=float(cfg["routed_scaling_factor"]),
                      shared=cfg.get("n_shared_experts", 0) > 0,
                      n_group=cfg.get("n_group", 1),
                      topk_group=cfg.get("topk_group", 1)),
        dtype=cfg.get("dtype", "bfloat16"), eps=cfg["rms_norm_eps"])


def _joyai(cfg):
    """``model_type: joyai_llm_flash``: the latent part of ``xing4_0``
    without YaRN (``rope_scaling`` null) over one residual stream, leading
    dense layers then sigmoid-routed experts of which this tree holds a
    share (``held_experts`` ``[lo, n]`` of ``n_routed_experts`` that the
    router scores, as ``afmoe`` states it), and the multi-token-prediction
    module LOADED: ``num_nextn_predict_layers`` modules (one at most) under
    the tree's ``"mtp"``."""
    if cfg.get("rope_scaling"):
        raise ValueError("joyai_llm_flash: no rope_scaling")
    if "hc_mult" in cfg:
        raise ValueError("joyai_llm_flash: one residual stream")
    record = _xing4(cfg)
    nextn = int(cfg.get("num_nextn_predict_layers", 0))
    held = cfg.get("held_experts")
    return dataclasses.replace(
        record, nextn=nextn,
        ffn_kinds=record.ffn_kinds + (MOE,) * nextn,
        moe=dataclasses.replace(
            record.moe,
            router_width=cfg.get("router_width", cfg["n_routed_experts"]),
            held=None if held is None else tuple(held)))


def _linear_or_full(cfg, period):
    """``attn_kinds`` of a stack of linear and full layers: ``layer_types``
    as written out, else the last of every ``period`` layers full."""
    n = cfg["num_hidden_layers"]
    types = cfg.get("layer_types")
    if types is None:
        types = ["full_attention" if (i + 1) % period == 0
                 else "linear_attention" for i in range(n)]
    if len(types) != n:
        raise ValueError("layer_types names %d layers of %d"
                         % (len(types), n))
    names = {"linear_attention": LINEAR, "full_attention": FULL}
    return tuple(names[t] for t in types)


def _qwen3_next(cfg):
    """``model_type: qwen3_next``: three gated-delta-rule layers to one
    gated softmax-attention layer (``full_attention_interval``, or
    ``layer_types`` written out), every layer with softmax-routed experts
    beside a sigmoid-gated shared expert, zero-centred RMSNorm gains,
    rotary positions on part of a head.  The multi-token-prediction module
    is not part of the record."""
    n = cfg["num_hidden_layers"]
    if cfg.get("decoder_sparse_step", 1) != 1 or cfg.get("mlp_only_layers"):
        raise ValueError("qwen3_next: every layer routed "
                         "(decoder_sparse_step 1, no mlp_only_layers)")
    if cfg.get("rope_scaling"):
        raise ValueError("qwen3_next: no rope_scaling")
    kinds = _linear_or_full(cfg, cfg.get("full_attention_interval"))
    width = cfg.get("router_width", cfg["num_experts"])
    held = tuple(cfg.get("held_experts") or (0, cfg["num_experts"]))
    return ModelConfig(
        n_heads=cfg["num_attention_heads"], block="pre_rms",
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope=True, rope_theta=float(cfg["rope_theta"]),
        partial_rotary=float(cfg.get("partial_rotary_factor", 1.0)),
        attn_kinds=kinds,
        linear=LinearConfig(
            k_heads=cfg["linear_num_key_heads"],
            v_heads=cfg["linear_num_value_heads"],
            k_dim=cfg["linear_key_head_dim"],
            v_dim=cfg["linear_value_head_dim"],
            conv=cfg["linear_conv_kernel_dim"]),
        norm_centred=True,
        ffn_kinds=(MOE,) * n,
        moe=MoEConfig(router_width=width,
                      top_k=cfg["num_experts_per_tok"], score="softmax",
                      route_norm=bool(cfg["norm_topk_prob"]), held=held,
                      shared=cfg["shared_expert_intermediate_size"] > 0,
                      shared_gate=True),
        dtype=cfg.get("dtype", "bfloat16"), eps=cfg["rms_norm_eps"])


def _ling3(cfg):
    """``model_type: ling3_flash`` (the language model of
    ``inclusionAI/Ling-3.0-flash-VL``; the name is ours, the published
    row states none): of every ``layer_group_size`` layers the last is
    latent attention WITHOUT a query bottleneck (``q_lora_rank`` null) under
    a head-wise sigmoid output gate, the others Kimi Delta Attention (a
    delta rule whose state decays by a vector over the key dimension,
    ``kda_safe_gate``); leading dense layers, then sigmoid-routed experts
    chosen within ``topk_group`` of ``n_group`` groups beside one shared
    expert.  No vision tower, no multi-token-prediction module: what the
    record cannot compute it refuses by key."""
    def refuse(key, why):
        raise ValueError("ling3_flash: %s %r: %s" % (key, cfg.get(key), why))

    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        if any(cfg.get(key) or ()):
            refuse(key, "a nonzero SwiGLU limit clamps the experts' hidden "
                   "activations in a form no source here gives; only layers "
                   "whose limit is 0 are served")
    for key in ("use_nGPT", "scale_router_input", "value_norm",
                "up_proj_norm", "use_mla_nope", "use_kda_lora",
                "rope_scaling", "num_nextn_predict_layers",
                "num_kv_heads_for_linear_attn"):
        if cfg.get(key):
            refuse(key, "not computed here")
    for key, want in (("kda_safe_gate", True), ("no_kda_lora", True),
                      ("linear_silu", True), ("group_norm_size", 1),
                      ("score_function", "sigmoid"),
                      ("moe_router_enable_expert_bias", True),
                      ("gated_attention_proj_granularity_type", "head_wise"),
                      ("rotary_dim", cfg["qk_rope_head_dim"])):
        if cfg.get(key, want) != want:
            refuse(key, "only %r is computed here" % (want,))
    n = cfg["num_hidden_layers"]
    heads, dim = cfg["num_attention_heads"], cfg["head_dim"]
    held = cfg.get("held_experts")
    return ModelConfig(
        n_heads=heads, block="pre_rms", rope=True,
        rope_theta=float(cfg["rope_theta"]),
        attn_kinds=_linear_or_full(cfg, cfg.get("layer_group_size")),
        latent=LatentConfig(
            q_rank=cfg.get("q_lora_rank"), kv_rank=cfg["kv_lora_rank"],
            nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
            v=cfg["v_head_dim"], head_gate=True),
        linear=LinearConfig(
            k_heads=heads, v_heads=heads, k_dim=dim, v_dim=dim,
            conv=cfg["short_conv_kernel_size"], decay="channel",
            gate="sigmoid", lower_bound=float(cfg["kda_lower_bound"])),
        ffn_kinds=tuple(DENSE if i < cfg["first_k_dense_replace"] else MOE
                        for i in range(n)),
        moe=MoEConfig(router_width=cfg.get("router_width",
                                           cfg["num_experts"]),
                      top_k=cfg["num_experts_per_tok"], score="sigmoid",
                      route_norm=bool(cfg["norm_topk_prob"]),
                      route_scale=float(cfg["routed_scaling_factor"]),
                      held=None if held is None else tuple(held),
                      shared=cfg["moe_shared_expert_intermediate_size"] > 0,
                      n_group=cfg["n_group"], topk_group=cfg["topk_group"]),
        dtype=cfg.get("dtype", "bfloat16"), eps=cfg["rms_norm_eps"])


def _granite_hybrid(cfg):
    """``model_type: granitemoehybrid`` WITHOUT routed experts
    (``num_local_experts`` 0: IBM Granite 4.0-H Micro): ``layer_types`` of
    ``mamba`` (a Mamba-2 state-space mixer, ``LinearConfig.rule == "ssd"``)
    and ``attention`` (plain grouped-query attention with NO position signal
    at all, ``position_embedding_type`` ``nope``), a dense gated-SiLU feed
    forward of ``shared_intermediate_size`` after EVERY mixer, Granite's four
    multipliers and a tied head.  What the record cannot compute it refuses
    by key."""
    def refuse(key, why):
        raise ValueError("granitemoehybrid: %s %r: %s"
                         % (key, cfg.get(key), why))

    for key in ("num_local_experts", "num_experts_per_tok", "attention_bias",
                "mamba_proj_bias", "rope_scaling"):
        if cfg.get(key):
            refuse(key, "not computed here (the routed experts beside a "
                   "state-space mixer are ROADMAP's)"
                   if key.startswith("num_") else "not computed here")
    for key, want in (("position_embedding_type", "nope"),
                      ("normalization_function", "rmsnorm"),
                      ("hidden_act", "silu"), ("mamba_conv_bias", True),
                      ("tie_word_embeddings", True),
                      ("intermediate_size", cfg["shared_intermediate_size"]),
                      ("mamba_expand", cfg["mamba_n_heads"]
                       * cfg["mamba_d_head"] // cfg["hidden_size"])):
        if cfg.get(key, want) != want:
            refuse(key, "only %r is computed here" % (want,))
    types = cfg["layer_types"]
    n = cfg["num_hidden_layers"]
    names = {"mamba": LINEAR, "attention": FULL}
    if len(types) != n or set(types) - set(names):
        raise ValueError("granitemoehybrid: layer_types names %d layers of "
                         "%d, of kinds %r" % (len(types), n, set(types)))
    heads = cfg["num_attention_heads"]
    return ModelConfig(
        n_heads=heads, block="pre_rms",
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or cfg["hidden_size"] // heads,
        rope=False, attn_kinds=tuple(names[t] for t in types),
        linear=LinearConfig(
            k_heads=cfg["mamba_n_groups"], v_heads=cfg["mamba_n_heads"],
            k_dim=cfg["mamba_d_state"], v_dim=cfg["mamba_d_head"],
            conv=cfg["mamba_d_conv"], rule="ssd"),
        ffn_kinds=(DENSE,) * n,
        embed_mult=float(cfg["embedding_multiplier"]),
        residual_mult=float(cfg["residual_multiplier"]),
        attn_scale=float(cfg["attention_multiplier"]),
        logits_div=float(cfg["logits_scaling"]), tied=True, plain_full=True,
        dtype=cfg.get("dtype", "bfloat16"), eps=cfg["rms_norm_eps"])
