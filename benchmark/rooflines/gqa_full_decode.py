"""Operations and bytes of one call of the paged flash-decode kernel on a FULL
grouped-query layer whose heads are ``head_dim`` wide (one call per full layer
per decode step), from the configuration's shapes and the tokens the DECODING
lanes hold: what the mathematics needs, whatever lays the pool out.

Each decoding lane reads the keys and values of every token it holds, once:
``num_key_value_heads x head_dim`` elements a token for K and as many for V
(2 x 2 x 256 x 2 B = 2048 bytes a token a layer in bfloat16), plus its query
and its output; 2 x 2 flops per query head per key element; bytes bound it.
That is ``rooflines/gqa_window_decode.py``'s count with nothing capped (a full
layer has no window): the same kernel, the same arithmetic, so it is that
file's functions under this kernel's name.  Lanes still in prefill ride the
step and the kernel reads what they hold, but no decode step needs it: the
caller counts the decoding lanes' tokens only."""

from benchmark.lib.files import load_module

_gqa = load_module("rooflines", "gqa_window_decode")
ops_and_bytes = _gqa.ops_and_bytes
roofline_seconds = _gqa.roofline_seconds
