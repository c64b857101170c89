"""Operations and bytes of one call of the absorbed latent-attention decode
kernel (one call per layer per decode step), from the configuration's shapes
and the tokens the decoding lanes hold: WHAT THE MATHEMATICS NEEDS, whatever
lays the pool out or implements the kernel.

Each decoding lane reads the cached row of every token it holds, once, for all
heads: ``kv_lora_rank + qk_rope_head_dim`` numbers a token (576: 1152 bytes in
bfloat16; the 64 lanes of padding a pool row carries are not needed and not
counted), plus its query rows in (heads x 576) and its outputs out (heads x
``kv_lora_rank``).  Per head and cached token the absorbed form multiplies 576
numbers for the score and ``kv_lora_rank`` for the accumulation: 2 x heads x
(576 + 512) flops a token.  At 60 flops a byte against the chip's 240, bytes
bound it."""


def ops_and_bytes(cfg, lanes, live_tokens, itemsize=2):
    """(flops, bytes) of one call: ``lanes`` lanes decoding one token each
    against ``live_tokens`` cached tokens in all."""
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    width = rank + cfg["qk_rope_head_dim"]
    cache_bytes = live_tokens * width * itemsize
    qo_bytes = lanes * heads * (width + rank) * itemsize
    flops = 2.0 * heads * (width + rank) * live_tokens
    return flops, cache_bytes + qo_bytes


def roofline_seconds(cfg, lanes, live_tokens, peaks):
    flops, nbytes = ops_and_bytes(cfg, lanes, live_tokens)
    return max(flops / peaks["bf16_flops_s"], nbytes / peaks["hbm_bytes_s"])
