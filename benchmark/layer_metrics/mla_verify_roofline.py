"""Share (%) of its roofline that the absorbed latent-attention kernel reaches
in a VERIFY step (two query rows a lane): the least time its calls in the
traced window could take on the published peaks
(``rooflines/mla_latent_verify.py``: the cached rows read once a decoding
lane; bytes bound it) over the device time they took.  The calls are the
Pallas operations inside ``jit_step_all`` (named ``attn ...`` in the stack's
layers and ``mtp ...`` in the module's, by their scopes) whose result is the
absorbed outputs' shape at ``spec_k + 1`` rows a head (``[lanes, 1, heads x
rows, row]``); the tokens the decoding
lanes hold at each traced step come from the loop recorder's request records
(``lib/latent.py::decoding_tokens``), as ``mla_decode_roofline`` takes them.
Lanes still in prefill ride the step masked to position 0 and read one page:
they are left out of the least time.  Layer: Pallas kernels."""

from benchmark.lib import latent
from benchmark.lib.files import load_module


def read(art, ctx):
    cfg = ctx.config
    rows = cfg.get("deployment", {}).get("spec_k", 0) + 1
    if "kv_lora_rank" not in cfg or rows < 2:
        return None
    shape = "[%d,1,%d,%d]" % (cfg["deployment"]["slots"],
                              cfg["num_attention_heads"] * rows,
                              latent.row_lanes(cfg))
    # (the Pallas calls alone: the compiler's own operations that make or
    # take the kernel's operands have the same shape, and are named by
    # their opcode)
    calls = latent.kernel_calls(
        art, lambda o: o.module in latent.DECODE and o.name.endswith(shape)
        and o.name.startswith(("attn ", "mtp ")))
    seconds = sum(o.self_dur for o in calls) / 1e9
    held = latent.decoding_tokens(art)
    if not calls or not seconds or held is None:
        return None
    tokens, lanes = held
    least = len(calls) * load_module(
        "rooflines", "mla_latent_verify").roofline_seconds(
            cfg, lanes, tokens, rows, ctx.peaks())
    return 100.0 * least / seconds
