"""Work of one step's fused STaMP prefill kernels, over all layers.

Per layer a chunk row set of ``rows`` tokens goes through four integer
GEMMs: merged QKV, the head-split out-proj, the dual gate/up (one input,
two weights) and the down-proj.  Operations are ``2 * rows * K * N`` at
the int8 peak; bytes are the int8 weight codes, the bfloat16 activation
read and written, and the float32 scale and zero point per output
channel.  The sequence transforms are not counted: they are how this
implementation computes the layer, not work the layer needs.
"""


def _sites(c: dict) -> list:
    """(K, N, weights sharing one input) per GEMM call of one layer."""
    d, ff = c["hidden_size"], c["intermediate_size"]
    hd = c.get("head_dim") or d // c["num_attention_heads"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    return [(d, q + 2 * kv, 1), (q, d, 1), (d, ff, 2), (ff, d, 1)]


def step(c: dict, rows: int) -> tuple:
    """(operations, HBM bytes) of one step whose prefill region holds
    ``rows`` chunk tokens."""
    ops = byts = 0
    for k, n, w in _sites(c):
        ops += 2 * rows * k * n * w
        byts += w * k * n + rows * k * 2 + rows * n * 2 + w * n * 8
    layers = c["num_hidden_layers"]
    return ops * layers, byts * layers


CALLS_PER_LAYER = 4
