"""Operations and bytes of one call of the paged flash-decode kernel
(``ops/pallas_kernels.py::paged_flash_decode``, one call per layer per decode
step), from the configuration's shapes and the tokens each lane holds.

What the algorithm needs: each lane reads the keys and values of the tokens
it holds, once (``live_tokens`` summed over the lanes), plus its query and
its output; 2 x 2 flops per key element and per value element.  Bytes bound
it on every shape here (operational intensity about 1 flop per byte), so the
roofline time is bytes over the published HBM bandwidth.  Shares are of the
published peaks (``lib/peaks.py``), for float32 work too."""


def ops_and_bytes(cfg, lanes, live_tokens, itemsize=4):
    """(flops, bytes) of one call: ``lanes`` lanes decoding one token each
    against ``live_tokens`` cached tokens in all."""
    d = cfg["hidden_size"]
    kv_bytes = 2 * live_tokens * d * itemsize          # K and V rows read
    qo_bytes = 2 * lanes * d * itemsize                # q in, o out
    flops = 4.0 * live_tokens * d                      # q.k and p.v
    return flops, kv_bytes + qo_bytes


def roofline_seconds(cfg, lanes, live_tokens, peaks):
    flops, nbytes = ops_and_bytes(cfg, lanes, live_tokens)
    return max(flops / peaks["bf16_flops_s"], nbytes / peaks["hbm_bytes_s"])
