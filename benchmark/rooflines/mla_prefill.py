"""Operations and bytes of one call of the expanded latent-attention prefill
kernel (one call per layer per prompt chunk), from the configuration's shapes
and where the chunk starts.

What the algorithm needs: per head and visible (query, key) pair, ``qk_nope +
qk_rope`` multiply-adds for the score and ``v_head_dim`` for the accumulation
(2 x heads x (192 + 128) flops a pair); a chunk of ``rows`` queries that
starts at ``start`` sees ``rows x start + rows (rows + 1) / 2`` pairs a head.
Bytes: the history's cached rows once (576 numbers a token) and the chunk's
queries in and outputs out.  The re-expansion of the cached latents through
``W_kvb`` is how this kernel gets its keys and values, not useful work, and is
NOT counted.  Compute bounds it from a few thousand tokens of history on."""


def ops_and_bytes(cfg, start, rows, itemsize=2):
    """(flops, bytes) of one call: ``rows`` query rows at positions
    ``start .. start + rows - 1``."""
    heads = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    v = cfg["v_head_dim"]
    pairs = rows * start + rows * (rows + 1) / 2.0
    flops = 2.0 * heads * (qk + v) * pairs
    width = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    nbytes = (start + rows) * width * itemsize \
        + rows * heads * (qk + v) * itemsize
    return flops, nbytes


def roofline_seconds(cfg, start, rows, peaks):
    flops, nbytes = ops_and_bytes(cfg, start, rows)
    return max(flops / peaks["bf16_flops_s"], nbytes / peaks["hbm_bytes_s"])
