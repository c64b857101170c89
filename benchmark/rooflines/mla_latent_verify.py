"""Operations and bytes of one call of the absorbed latent-attention kernel
in a VERIFY step (``rows`` query rows a lane: the last token and the drafts
behind it; one call per layer per step, the module's layer among them), from
the configuration's shapes and the tokens the decoding lanes hold: what the
mathematics needs, whatever lays the pool out or implements the kernel.

``rooflines/mla_latent_decode.py`` at ``rows`` rows a lane: the cached rows
are still read ONCE a lane (``kv_lora_rank + qk_rope_head_dim`` numbers a
token, for all heads and all rows), the query rows in and the outputs out
``rows`` times, and the flops ``rows`` times.  At ``rows`` x 60 flops a byte
against the chip's 240, bytes still bound two rows."""


def ops_and_bytes(cfg, lanes, live_tokens, rows, itemsize=2):
    """(flops, bytes) of one call: ``lanes`` lanes of ``rows`` query rows
    each against ``live_tokens`` cached tokens in all."""
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    width = rank + cfg["qk_rope_head_dim"]
    cache_bytes = live_tokens * width * itemsize
    qo_bytes = rows * lanes * heads * (width + rank) * itemsize
    flops = 2.0 * rows * heads * (width + rank) * live_tokens
    return flops, cache_bytes + qo_bytes


def roofline_seconds(cfg, lanes, live_tokens, rows, peaks):
    flops, nbytes = ops_and_bytes(cfg, lanes, live_tokens, rows)
    return max(flops / peaks["bf16_flops_s"], nbytes / peaks["hbm_bytes_s"])
