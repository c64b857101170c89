"""Analytic FLOP counts, from shapes alone.

``train_flops_per_sample`` follows ``bench.py::model_train_flops_per_sample``
(copied convention): a layer's forward is 2 x MACs, training costs three
forwards (forward, input gradient, weight gradient), and the first
parameterised layer has no input gradient."""


def conv_out(size, k, stride, padding):
    if padding == "SAME":
        return -(-size // stride)
    return (size - k) // stride + 1


def layer_forward_flops(layers, in_hw, in_ch):
    """[(kind, flops per sample)] for every conv / dense layer of a config's
    ``layers`` list, walking the activation shape from ``in_hw`` x ``in_ch``."""
    h, w = in_hw
    c = in_ch
    flat = None
    out = []
    for layer in layers:
        kind = layer["type"]
        if kind == "conv":
            k, s = layer["k"], layer["stride"]
            h, w = (conv_out(h, k, s, layer["padding"]),
                    conv_out(w, k, s, layer["padding"]))
            out.append(("conv", 2.0 * k * k * c * layer["n"] * h * w))
            c = layer["n"]
        elif kind == "pool":
            k, s = layer["k"], layer["stride"]
            h, w = (h - k) // s + 1, (w - k) // s + 1
        elif kind in ("dense", "softmax"):
            n_in = flat if flat is not None else h * w * c
            out.append(("dense", 2.0 * n_in * layer["n"]))
            flat = layer["n"]
    return out


def train_flops_per_sample(layers, in_hw, in_ch, kinds=("conv", "dense")):
    total = 0.0
    first = True
    for kind, f in layer_forward_flops(layers, in_hw, in_ch):
        cost = 3.0 * f - (f if first else 0.0)
        first = False
        if kind in kinds:
            total += cost
    return total
