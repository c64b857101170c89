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

A record with ``attn_kinds`` naming both ``sliding`` and ``full`` layers has
two kinds of KV cache (``kinds``): the engine keeps a page table and an
allocator for each.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

SLIDING, FULL = "sliding", "full"
DENSE, MOE = "dense", "moe"


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """The routed feed forward of one model: ``router_width`` experts are
    scored, ``top_k`` chosen per token, and ``held = (lo, n)`` says which
    of them this param tree carries (an expert-parallel share; the others'
    part of the sum is another chip's)."""
    router_width: int
    top_k: int = 1
    score: str = "softmax"            # | "sigmoid"
    route_norm: bool = False          # weights / their sum over the chosen
    route_scale: float = 1.0
    held: Optional[Tuple[int, int]] = None    # None: all of them
    shared: bool = False              # a shared expert beside the routed

    def held_range(self):
        return self.held if self.held is not None else (0, self.router_width)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    n_heads: int
    block: str = "pre_ln"             # | "sandwich"
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

    def __post_init__(self):
        if self.block not in ("pre_ln", "sandwich"):
            raise ValueError("unknown block %r" % (self.block,))
        if self.attn_kinds is not None:
            bad = set(self.attn_kinds) - {SLIDING, FULL}
            if bad:
                raise ValueError("unknown attention kind(s) %r" % (bad,))
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
    def by_kind(self):
        """The stack names its layers' attention kinds."""
        return self.attn_kinds is not None

    def kind(self, layer):
        """Which cache kind layer ``layer`` reads and writes."""
        if self.attn_kinds is None:
            return FULL
        return self.attn_kinds[layer]

    def layer_rope(self, layer):
        if self.attn_kinds is None:
            return self.rope
        return self.attn_kinds[layer] == SLIDING

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
    keys), by ``model_type``.  ``afmoe`` also reads two keys of a
    deployment's share where they are given: ``held_experts`` ``[lo, n]``
    (this tree's experts, of ``router_width`` that the router scores)."""
    family = cfg.get("model_type")
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
