"""Operations and bytes of the state-space rule over one prompt chunk of one
Mamba layer (one call of the chunk kernel per layer per chunk), from the
configuration's shapes: the CHUNKED form as the layer's equations write it,
with inner chunks of ``inner`` rows::

    O = (C exp(gam)) S + tril((C B^T) exp(gam_i - gam_j)) (dt x)
    S <- exp(gam_Q) S + (B exp(gam_Q - gam))^T (dt x)

Per row: ``C B^T`` once a group (its lower triangle: (inner + 1) x state size
flops), and per head ``C S`` and the state's update (2 x state size x head
size each) and the triangle times ``dt x`` ((inner + 1) x head size): 64 x (4
x 128 x 64 + 129 x 64) + 129 x 128 = 2.6 MFLOP a row at inner chunks of 128.
The bytes: each row's ``[x | B | C]``, ``dt`` and decay in and outputs out at
the model's width, the lane's state in and out (float32).  At 1024 rows: 2.7
GFLOP (14 us at the bfloat16 peak), 17 MB of rows and 4 MiB of state (26 us):
bytes bound it.  The share this gives will read LOW: the kernel's matmuls are
float32 at the highest precision (six passes of the matrix unit) and its rows
come and go in float32."""


def ops_and_bytes(cfg, rows, inner, itemsize=2):
    """(flops, bytes) of one call over ``rows`` rows of one lane."""
    h, dv = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    dk, groups = cfg["mamba_d_state"], cfg["mamba_n_groups"]
    flops = rows * (h * (4.0 * dk * dv + (inner + 1) * dv)
                    + groups * (inner + 1) * dk)
    nbytes = rows * (h * dv + 2 * groups * dk + 2 * h + h * dv) * itemsize \
        + 2 * h * dk * dv * 4
    return flops, nbytes


def roofline_seconds(cfg, rows, inner, peaks):
    flops, nbytes = ops_and_bytes(cfg, rows, inner)
    return max(flops / peaks["bf16_flops_s"], nbytes / peaks["hbm_bytes_s"])
