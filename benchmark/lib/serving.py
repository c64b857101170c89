"""What the client log says about the lanes, for readers that need shapes
the trace does not carry."""


def mean_live_tokens(log, t0, t1, samples=200):
    """(mean cached tokens summed over the requests in flight, mean requests
    in flight) over host times [t0, t1].  A request's cache grows from its
    prompt to prompt + n_new between send and reply; taken as linear in time,
    which leaves out that the prompt's chunks come first."""
    tokens = lanes = 0.0
    for i in range(samples):
        t = t0 + (t1 - t0) * (i + 0.5) / samples
        for r in log:
            done = r["t_done"]
            if r["t_send"] <= t and (done is None or done > t):
                share = ((t - r["t_send"]) / (done - r["t_send"])
                         if done is not None else 0.5)
                tokens += r["prompt_len"] + share * r["n_new"]
                lanes += 1
    return tokens / samples, lanes / samples
