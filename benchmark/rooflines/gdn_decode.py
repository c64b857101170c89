"""Operations and bytes of one call of the gated delta rule's recurrent step
(one call per linear layer per decode step), from the configuration's shapes
and the lanes that decode: WHAT THE MATHEMATICS NEEDS, whatever implements it.

A decoding lane reads its recurrent state (``linear_num_value_heads`` x
``linear_key_head_dim`` x ``linear_value_head_dim`` float32: 2 MiB at 32 x 128
x 128) and writes it back, reads and writes its convolution tail (3 rows of
the 8192 channels of ``[q | k | v]``, bfloat16), takes the new row's q, k, v
in and gives the heads' outputs out.  A lane that is prefilling or empty
needs nothing.  Per value head the rule multiplies the state by three
vectors: 6 x key dim x value dim flops; at 1.5 flops a byte against the
chip's 240, bytes bound it."""


def ops_and_bytes(cfg, lanes, itemsize=2):
    """(flops, bytes) of one call with ``lanes`` lanes decoding."""
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    channels = 2 * hk * dk + hv * dv
    state = 2 * hv * dk * dv * 4                  # in and out, float32
    tail = 2 * (cfg["linear_conv_kernel_dim"] - 1) * channels * itemsize
    rows = (channels + hv * dv) * itemsize        # q, k, v in; o out
    return lanes * 6.0 * hv * dk * dv, lanes * (state + tail + rows)


def roofline_seconds(cfg, lanes, peaks):
    flops, nbytes = ops_and_bytes(cfg, lanes)
    return max(flops / peaks["bf16_flops_s"], nbytes / peaks["hbm_bytes_s"])
