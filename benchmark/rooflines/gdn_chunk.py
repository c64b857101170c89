"""Operations and bytes of the gated delta rule over one prompt chunk of one
linear layer (one call of the sequential pass per layer per chunk), from the
configuration's shapes: WHAT THE MATHEMATICS NEEDS, whatever implements it.

The flops are the RECURRENT form's: per row and value head the state is
multiplied by three vectors, 6 x key dim x value dim (32 heads x 6 x 128 x
128 = 3.1 MFLOP a row); the chunked order spends more (its intra-chunk
products and the triangular solve) and none of that is counted.  The bytes:
each row's q, k, v in and outputs out at the model's width, the lane's state
in and out (float32).  At 1024 rows: 3.2 GFLOP (16 us at the bfloat16 peak),
25 MB of rows and 4 MiB of state (36 us): bytes bound it, and the least time
is some 36 us a call.  The share this gives will read LOW: the pass is 16
dependent steps a head of four small float32 dots each."""


def ops_and_bytes(cfg, rows, itemsize=2):
    """(flops, bytes) of one call over ``rows`` rows of one lane."""
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    channels = 2 * hk * dk + hv * dv
    flops = rows * 6.0 * hv * dk * dv
    nbytes = rows * (channels + hv * dv) * itemsize + 2 * hv * dk * dv * 4
    return flops, nbytes


def roofline_seconds(cfg, rows, peaks):
    flops, nbytes = ops_and_bytes(cfg, rows)
    return max(flops / peaks["bf16_flops_s"], nbytes / peaks["hbm_bytes_s"])
