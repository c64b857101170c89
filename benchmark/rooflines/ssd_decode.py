"""Operations and bytes of one call of the state-space rule's recurrent step
(one call per Mamba layer per decode step), from the configuration's shapes
and the lanes that decode: WHAT ANY IMPLEMENTATION MUST MOVE, however it tiles
or packs.

A decoding lane reads its recurrent state (``mamba_n_heads`` x
``mamba_d_state`` x ``mamba_d_head`` float32: 2 MiB at 64 x 128 x 64) and
writes it back, takes the new row's ``[x | B | C]``, ``dt`` and decay in and
gives the heads' outputs out, at the model's width.  A lane that is prefilling
or empty needs nothing.  The convolution and its tail run beside the kernel
(plain XLA): neither their time nor their bytes are here.  Per head the rule
scales the state, adds an outer product and multiplies by one vector: 5 x
state size x head size flops; at 0.6 flops a byte against the chip's 240,
bytes bound it."""


def ops_and_bytes(cfg, lanes, itemsize=2):
    """(flops, bytes) of one call with ``lanes`` lanes decoding."""
    h, dv = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    dk, groups = cfg["mamba_d_state"], cfg["mamba_n_groups"]
    state = 2 * h * dk * dv * 4                   # in and out, float32
    rows = (h * dv + 2 * groups * dk + 2 * h + h * dv) * itemsize
    return lanes * 5.0 * h * dk * dv, lanes * (state + rows)


def roofline_seconds(cfg, lanes, peaks):
    flops, nbytes = ops_and_bytes(cfg, lanes)
    return max(flops / peaks["bf16_flops_s"], nbytes / peaks["hbm_bytes_s"])
