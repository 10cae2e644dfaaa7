"""Work of one step's single-token integer decode kernels, over all layers.

Per layer the decode slot array (``rows`` tokens, one per slot) goes
through five integer GEMMs: merged QKV, out-proj, gate, up and down.
Operations are ``2 * rows * K * N`` at the int8 peak; bytes are the int8
weight codes, the bfloat16 activation read and written, and the float32
scale and zero point per output channel.
"""


def _sites(c: dict) -> list:
    d, ff = c["hidden_size"], c["intermediate_size"]
    hd = c.get("head_dim") or d // c["num_attention_heads"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    return [(d, q + 2 * kv), (q, d), (d, ff), (d, ff), (ff, d)]


def step(c: dict, rows: int) -> tuple:
    """(operations, HBM bytes) of one step's decode kernels."""
    ops = byts = 0
    for k, n in _sites(c):
        ops += 2 * rows * k * n
        byts += k * n + rows * k * 2 + rows * n * 2 + n * 8
    layers = c["num_hidden_layers"]
    return ops * layers, byts * layers


CALLS_PER_LAYER = 5
