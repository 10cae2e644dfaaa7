"""Work of the grouped expert kernel's calls (``moe_grouped_matmul``):
the SwiGLU experts this chip holds, run on the rows routed to them.

Each held row goes through three GEMMs of its expert, gate and up
(``d -> f``) and down (``f -> d``): ``2 * rows * 3 * d * f`` operations
at the int8 peak.  Bytes are the int8 codes of every expert that got a
row (each streams once per call), each row's int8 input and bfloat16
output, and the float32 scale and zero point of each output channel of
those experts.  Padding rows and empty tiles are not counted, so any
implementation of the layer is read against the same work.
"""


def work(c: dict, rows_held: float, expert_groups: float) -> tuple:
    """(operations, HBM bytes) of calls that together ran ``rows_held``
    rows over ``expert_groups`` experts with at least one row (the
    engine's ``moe_rows_held`` and ``moe_expert_groups``)."""
    d, f = c["hidden_size"], c["intermediate_size"]
    ops = 2 * rows_held * 3 * d * f
    byts = expert_groups * (3 * d * f + (2 * f + d) * 8) \
        + rows_held * (d + 2 * d)
    return ops, byts
