"""Operations and bytes of one call of Kimi delta attention's recurrent step
(one call per KDA layer per decode step), from the configuration's shapes and
the lanes that decode: WHAT THE MATHEMATICS NEEDS, whatever implements it.

A decoding lane reads its recurrent state (``num_attention_heads`` x
``head_dim`` x ``head_dim`` float32: 2 MiB at 32 x 128 x 128) and writes it
back, reads and writes its convolution tail (3 rows of the 12288 channels of
``[q | k | v]``, bfloat16), takes the new row's q, k, v and its decay a key
channel in and gives the heads' outputs out.  A lane that is prefilling or
empty needs nothing.  Per head the rule scales the state's rows and multiplies
it by three vectors: 7 x key dim x value dim flops; at under 2 flops a byte
against the chip's 240, bytes bound it."""


def ops_and_bytes(cfg, lanes, itemsize=2):
    """(flops, bytes) of one call with ``lanes`` lanes decoding."""
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    channels = 3 * h * d
    state = 2 * h * d * d * 4                     # in and out, float32
    tail = 2 * (cfg["short_conv_kernel_size"] - 1) * channels * itemsize
    rows = (channels + 2 * h * d) * itemsize      # q, k, v, g in; o out
    return lanes * 7.0 * h * d * d, lanes * (state + tail + rows)


def roofline_seconds(cfg, lanes, peaks):
    flops, nbytes = ops_and_bytes(cfg, lanes)
    return max(flops / peaks["bf16_flops_s"], nbytes / peaks["hbm_bytes_s"])
