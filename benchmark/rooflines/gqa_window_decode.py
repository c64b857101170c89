"""Operations and bytes of one call of the paged flash-decode kernel on a
grouped-query layer whose heads are ``head_dim`` wide (one call per layer per
decode step), from the configuration's shapes and the tokens each lane holds.

What the algorithm needs: each lane reads the keys and values of the tokens
its query can see, once: all it holds on a full layer, at most
``sliding_window`` of them on a sliding layer (the caller caps them), at
``num_key_value_heads x head_dim`` elements a token for K and as many for V
(4 KB a token a layer in bfloat16 at 8 x 128), plus its query and its output;
2 x 2 flops per query head per key element.  Bytes bound it, so the roofline
time is bytes over the published HBM bandwidth."""


def ops_and_bytes(cfg, lanes, live_tokens, itemsize=2):
    """(flops, bytes) of one call: ``lanes`` lanes decoding one token each
    against ``live_tokens`` visible cached tokens in all."""
    heads, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    kv_bytes = 2 * live_tokens * kv * dh * itemsize     # K and V rows read
    qo_bytes = 2 * lanes * heads * dh * itemsize        # q in, o out
    flops = 4.0 * live_tokens * heads * dh              # q.k and p.v
    return flops, kv_bytes + qo_bytes


def roofline_seconds(cfg, lanes, live_tokens, peaks):
    flops, nbytes = ops_and_bytes(cfg, lanes, live_tokens)
    return max(flops / peaks["bf16_flops_s"], nbytes / peaks["hbm_bytes_s"])
