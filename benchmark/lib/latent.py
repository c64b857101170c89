"""What the latent-attention readers share: the kernels' calls in the trace,
and what the loop recorder's request records say the lanes held.

The profiler names a Pallas call by the scope around it (``attn.latent``,
which ``lib/trace.py::short_name`` cuts to ``attn``) and its result's shape:
the decode kernel returns the absorbed outputs ``[lanes, 1, heads, row]`` in
``jit_step_all``, the prefill kernel the heads' outputs ``[1, heads, chunk,
v_head_dim]`` in ``jit_chunk_slot``; the row write under the same scope
returns a pool.  A program without these kernels (the parent) has no such
operations, and every reader returns None."""

from benchmark.lib import spans

DECODE = ("step_all", "jit_step_all")
PREFILL = ("chunk_slot", "jit_chunk_slot")


def row_lanes(cfg):
    """Lanes of a pool row: the latent and the rotated key, in whole tiles."""
    return -(-(cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) // 128) * 128


def is_attn(op):
    return op.module in DECODE + PREFILL and op.name.startswith("attn ")


def is_decode_kernel(op, cfg):
    return (op.module in DECODE and op.name.startswith("attn ")
            and op.name.endswith("[%d,1,%d,%d]" % (
                cfg["deployment"]["slots"], cfg["num_attention_heads"],
                row_lanes(cfg))))


def is_prefill_kernel(op, cfg):
    return (op.module in PREFILL and op.name.startswith("attn ")
            and op.name.endswith("[1,%d,%d,%d]" % (
                cfg["num_attention_heads"],
                cfg["deployment"]["prefill_chunk"], cfg["v_head_dim"])))


def kernel_calls(art, keep):
    trace = art["trace"]
    if not trace["devices"] or not art.get("trace_host_window"):
        return []
    return [o for o in trace["devices"][0]["ops"] if keep(o)]


def decoding_tokens(art):
    """(mean cached tokens summed over the DECODING lanes, mean decoding
    lanes) at the decode dispatches inside the host's traced window, from
    the request records: a request decodes from its first token to its last,
    and holds its prompt and the tokens emitted so far.  Lanes still
    prefilling ride the step program too, but what they hold is not what a
    decode step needs to read."""
    found = spans.recorder(art)
    if found is None or not art.get("trace_host_window"):
        return None
    import numpy
    t, turns = found["tracing"], found["turns"]
    lo, hi = (int(x * 1e9) for x in art["trace_host_window"])
    keep = (turns[:, t.COL_STEP_PROGRAM] > 0) \
        & (turns[:, t.COL_STAMPS] >= lo) & (turns[:, t.COL_END] <= hi)
    at = turns[keep][:, t.COL_STAMPS + t.STEP_DISPATCH]
    if not len(at):
        return None
    tokens = numpy.zeros(len(at))
    lanes = numpy.zeros(len(at))
    for r in found["recorder"].requests():
        stamps = numpy.asarray(r.token_ns, numpy.int64)
        if not len(stamps):
            continue
        # the step dispatched at ``at`` reads what was emitted before it
        live = (at > stamps[0]) & (at <= stamps[-1])
        emitted = numpy.searchsorted(stamps, at, side="left")
        tokens += numpy.where(live, r.prompt_len + emitted, 0)
        lanes += live
    return float(tokens.mean()), float(lanes.mean())


def traced_chunks(art, chunk):
    """The expected start position of the prompt chunk dispatched in each
    prefill turn inside the host's traced window.  The engine advances ONE
    prefilling lane a turn, round robin (``LMEngine._serve_loop``), and the
    turn's record says that a chunk went but not whose: with ``k`` requests
    in prefill (admitted, no first token yet, chunks left) each is taken as
    1/k likely, and a request's progress is the sum of its shares over the
    prefill turns since its admission.  (Chunks spread evenly in TIME between
    admission and first token put a long prompt too far ahead while sixteen
    lanes share the turns, and read the kernel a half too fast in a window
    early in a round.)"""
    found = spans.recorder(art)
    if found is None or not art.get("trace_host_window"):
        return None
    import numpy
    t, turns = found["tracing"], found["turns"]
    lo, hi = (int(x * 1e9) for x in art["trace_host_window"])
    went = turns[turns[:, t.COL_PREFILL_PROGRAM] > 0]
    requests = [r for r in found["recorder"].requests()
                if r.admit and r.first_token]
    if not len(went) or not requests:
        return None
    admit = numpy.asarray([r.admit for r in requests])
    first = numpy.asarray([r.first_token for r in requests])
    chunks = numpy.asarray([-(-r.prompt_len // chunk) for r in requests])
    done = numpy.zeros(len(requests))
    starts = []
    for at in numpy.sort(went[:, t.COL_STAMPS + t.PREFILL_DISPATCH]):
        live = (admit <= at) & (at < first) & (done < chunks)
        k = int(live.sum())
        if not k:
            continue
        if lo <= at <= hi:
            starts.append(chunk * float(numpy.floor(done[live]).mean()))
        done[live] += 1.0 / k
    return starts
