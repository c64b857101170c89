"""Operations and bytes of Kimi delta attention over one prompt chunk of one
KDA layer (one call of the sequential pass per layer per chunk), from the
configuration's shapes: WHAT THE MATHEMATICS NEEDS, whatever implements it.

The flops are the RECURRENT form's: per row and head the state's rows are
scaled and the state is multiplied by three vectors, 7 x key dim x value dim
(32 heads x 7 x 128 x 128 = 3.7 MFLOP a row); the chunked order spends more
(its pairwise decays about reference rows, the intra-chunk products and the
triangular solve) and none of that is counted.  The bytes: each row's q, k, v
and decay in and outputs out at the model's width, the lane's state in and
out (float32).  At 1024 rows: 3.8 GFLOP (19 us at the bfloat16 peak), 42 MB
of rows and 4 MiB of state (56 us): bytes bound it.  The share this gives
will read LOW: the pass is 16 dependent steps a head of four small float32
dots each."""


def ops_and_bytes(cfg, rows, itemsize=2):
    """(flops, bytes) of one call over ``rows`` rows of one lane."""
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    flops = rows * 7.0 * h * d * d
    nbytes = rows * 5 * h * d * itemsize + 2 * h * d * d * 4
    return flops, nbytes


def roofline_seconds(cfg, rows, peaks):
    flops, nbytes = ops_and_bytes(cfg, rows)
    return max(flops / peaks["bf16_flops_s"], nbytes / peaks["hbm_bytes_s"])
