"""Operations and bytes of the grouped matmuls of one expert layer in one
decode step (``ops/moe.py::held_part``: gate, up and down projections of the
held experts over the sorted assignment rows), from the configuration's
shapes and what the step counted.

What the algorithm needs: the three matrices of each expert that was HIT,
once (an expert with no token is not read), plus the assignment rows in and
out; 2 flops per weight element per row routed to a held expert.  Bytes
bound it at decode sizes (half a token per held expert a step), so the
roofline time is bytes over the published HBM bandwidth."""


def ops_and_bytes(cfg, experts_hit, rows_held, rows_all, itemsize=2):
    """(flops, bytes) of one layer's three grouped matmuls: ``experts_hit``
    experts read, ``rows_held`` assignment rows computed for them, of
    ``rows_all`` rows that go in and come out."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weight_bytes = experts_hit * 3 * d * f * itemsize
    row_bytes = 2 * rows_all * d * itemsize         # rows in, rows out
    flops = 2.0 * rows_held * 3 * d * f
    return flops, weight_bytes + row_bytes


def roofline_seconds(cfg, experts_hit, rows_held, rows_all, peaks):
    flops, nbytes = ops_and_bytes(cfg, experts_hit, rows_held, rows_all)
    return max(flops / peaks["bf16_flops_s"], nbytes / peaks["hbm_bytes_s"])
