"""Pallas TPU kernels for STaMP's compute hot spots.

`<name>.py` holds the ``pl.pallas_call`` + BlockSpec tiling, `ops.py` the
jit'd wrappers (interpret-mode on CPU), `ref.py` the pure-jnp oracles.

Reference vs. fused execution
-----------------------------
STaMP linears run in one of two modes, selected by
``repro.core.stamp.StampConfig.execution``:

* ``"reference"`` (default) — the pure-jnp path: ``L·X``, the fake-quantized
  activation, the bf16 matmul output and ``L⁻¹(·)`` each materialize as a
  separate XLA tensor (four HBM round trips of the activation per linear).
  This is the numerics oracle and the only path for dense-basis transforms
  (dct/klt/dwt2d), per-block granularity and activation feature rotations.
* ``"fused"`` — `stamp_matmul.stamp_quant_matmul` runs transform →
  mixed-precision quantize (first ``num_hi`` tokens at ``hi_bits``, rest at
  ``lo_bits``) → int8×int8 GEMM with per-row/per-column scale correction →
  inverse transform → bias in a single VMEM residency: one HBM read of X and
  one write of Y.  Weights are pre-quantized once into signed-int8 buffers
  (`repro.core.stamp.prepare_linear` /
  `repro.models.lm.prepare_fused_weights`) instead of being dequantized to
  bf16 on every call.  Supports dwt/wht/none transforms, per-token
  granularity; ineligible configs silently fall back to the reference path
  with identical semantics.

Every prefill-path model linear is wired through the fused family
(`repro.models.lm.FUSED_SITES`); two sites get dedicated treatment:

* **out-proj** — `stamp_quant_matmul` also accepts the raw head-split
  ``(b, s, nh, hd)`` attention output.  The BlockSpec maps the full
  head-split tile per batch row and the kernel merges ``(nh, hd)`` on the
  in-VMEM tile right before the transform, so the head-merge reshape is
  fused with the stamped quantize instead of materializing a merged
  activation in HBM between attention and the projection.
* **gate/up pair** — `stamp_matmul.stamp_quant_dual_matmul` executes the
  SwiGLU front half as ONE kernel.  Execution model: grid ``(batch,
  N/block_n)`` exactly like the single kernel; on the first output-block
  step the shared MLP input's transform + mixed-precision quantize run
  once into VMEM scratch (int8 codes + per-token scale/zp), and **both**
  the gate and up GEMMs of every output block consume those same codes —
  the transform+quantize cost is paid once, not twice.  Each GEMM's result
  is inverse-transformed separately (``L⁻¹`` commutes with the weight
  multiplication but not with the gating nonlinearity), biases apply in
  the token domain, and the optional ``silu·mul`` epilogue combines the
  pair in-VMEM so only the product is written: one HBM read of the shared
  input, two int8 weight streams, one output write.  With
  ``epilogue="none"`` both projections are written (two outputs), still
  off the single shared quantize.

Grouped MoE execution
---------------------
The MoE expert einsums don't fit the per-sequence tiling above: after
routing, the activation is grouped by expert, not by sequence span, and
the sequence transform ``L`` does not commute with the dispatch gather,
so a per-group transform would change numerics.  Serving routes
dropless (`repro.models.layers.moe_route_dropless`) and
`stamp_matmul.stamp_quant_ragged_matmul_pallas` (wrapper
`ops.stamp_quant_ragged_matmul`) splits the work at the token boundary —
the **dispatch-once-quantize-once invariant**:

* the stamped round trip (transform → mixed-precision fake-quant →
  inverse) runs ONCE per token in XLA, shared verbatim with the router
  input, so fused and reference paths route bit-identically by
  construction;
* `repro.core.stamp.token_quantize` then produces one int8 code + scale
  + zero point per token, and the *codes* are gathered into each held
  expert's row group (``block_m``-aligned, so every row tile belongs to
  one expert) — the dispatch buffer moves int8, not bf16;
* ONE kernel walks grid ``(n_tiles, f/block_f)`` with a tile table
  (fetch tile | expert | rows) as its scalar-prefetch argument: empty
  tiles copy and write nothing, rows past a tile's count are zeroed
  in-kernel, and gate + up GEMMs consume the same gathered codes with
  the silu·mul epilogue and the grouped down-proj in VMEM scratch — the
  ``(rows, f)`` intermediates never reach HBM.

Expert weights prepare like every other site
(`prepare_fused_weights` stacks the scanned period as
``(nper, E, din, dout)`` int8) and shard expert-parallel over the
``'model'`` mesh axis through the existing suffix-strip rules
(`repro/sharding.py`).

The unified ragged serving step
-------------------------------
The paged engine dispatches ONE device program per step
(`repro.models.lm.paged_unified_step`): up to ``max_prefills`` prefill
chunk spans plus the decode slot array form a flattened token batch with
per-span ``(query_start, query_len)`` metadata from the scheduler.  Three
rules keep the kernels correct inside that program:

* **STaMP segment rule** — the sequence transform applies per sequence
  span, never across the flattened batch.  Spans are uniform (chunks pad
  to ``C`` tokens), so the unified step builds the prefill region
  **span-major** — ``(n_pf, C, d)``, one batch row per span — and the
  fused kernels see each span as its own grid row (whose
  transform+quantize scratch is already private).  Callers that do hold
  a flattened ``(b, n·C, d)`` carrier get the same rule through
  `repro.core.stamp.fold_segments` / the ``seg_len`` parameter on the
  stamp linears, and at the kernel level through
  `stamp_matmul.stamp_quant_segment_matmul_pallas`.  Decode spans are
  single tokens — their transform is the identity, which is why the
  decode region applies none.
* **Ragged attention** — `paged_attention.paged_ragged_attention`
  walks query spans: decode spans over their pages, chunk rows over their
  cached prefix, then causally over their raw chunk (the XLA fallback's
  result).  See the paged layout section below.
* **Decode-matmul dispatch by shape** — both regions share one trace, so
  the single-token integer matmul (below) keys on the token dim being 1:
  decode sub-tensors ``(S, 1, d)`` take it, chunk rows ``(n_pf, C>1, d)``
  cannot, and the all-decode step (n_pf = 0) IS the old decode graph.

Decode-shaped execution
-----------------------
Decode has no sequence axis, so its two kernels drop the transform and keep
only the mixed-precision memory layout:

* `decode_matmul.stamp_decode_matmul` — one token per slot against the same
  cached int8 weight buffers the prefill kernel uses (8-bit per-token
  activation quantize + integer GEMM; no per-step bf16 weight
  re-materialization).  Enabled via ``ServeConfig.fused_decode_matmul``.
* `cache_attention.cache_decode_attention` — fused attention over the
  *contiguous* packed mixed-precision KV cache (per-slot dense layout).

Paged-attention block layout
----------------------------
`paged_attention` serves the continuous-batching engine
(`serving/scheduler.py` + `serving/paged_kvcache.py`).  The cache is two
shared page pools instead of per-slot dense buffers:

* **hi pool** ``(NH, bs, kv, hd)`` int8 — pages holding the first
  ``num_hi`` logical tokens of each sequence (the attention-sink region)
  at 8 bits; ``num_hi % bs == 0`` so pages are single-precision.
* **lo pool** ``(NL, bs, kv·hd/2)`` uint8 — int4 nibble pairs packed along
  head_dim, with the page's f16 scales and zero points in
  ``lo_scale_zp``: a page is lane-dense and contiguous, so one async copy
  moves it.

Each span maps logical block ``k`` to a physical page through its block
table.  The kernel's grid is the spans; per span it walks only the span's
own int4 pages, ~512 tokens a step, double-buffered (page copies for the
next step start before this step's wait), and keeps the online-softmax
``(m, l, acc)`` in the span's output block.  The sink pages (a fixed few
per span) go through the XLA gather and merge with the kernel's
statistics, as the fallback merges its segments.  On a TPU the unified
step takes the kernel for every quantized pool it compiles for
(`lm.paged_kernel`; the geometries are one rule,
`paged_attention.unsupported`); elsewhere the fallback runs unless
``fused_cache_attention`` forces the kernel in interpret mode.

Hybrid dense + paged layout
---------------------------
Hybrid stacks (Jamba-style Mamba + attention) split their serving state
across two layouts inside one engine step:

* **attention layers** — the paged pools above, written through the
  combined ragged scatter and read by `paged_ragged_attention` /
  `paged_decode_attention` exactly as in the attention-only case;
* **Mamba layers** — a **slot-dense** state pool
  (`serving/paged_kvcache.init_ssm_slots`): per slot, one f32
  ``(heads, head_dim, ssm_state)`` state matrix and a bf16 conv tail.
  Recurrent state is fixed-size per request, so paging buys nothing —
  there is nothing proportional to sequence length to reclaim — and the
  pool indexes by *slot*, with row ``num_slots`` as the null slot (the
  scatter target for unused prefill chunk rows, mirroring the null page).
  The SSM mixer itself stays XLA (`models/layers.ssd_chunked` carries
  ``init_state`` across chunk spans; decode is a batched one-token
  recurrence with inactive slots masked) — it reads no pages, so it needs
  no Pallas treatment; the Mamba in/out projections still route through
  the fused STaMP kernels above.

Kernel contract registry
------------------------
`specs.py` keeps a capture registry (``KERNEL_EXAMPLES`` /
``kernel_spec(name)``): one representative example call per kernel
family, captured by intercepting ``pallas_call`` so the grid, BlockSpecs,
scratch shapes and concrete scalar-prefetch tables are recorded without
executing the kernel.  The static contract checker
(``python -m repro.analysis.contracts``) evaluates every index map over
the full grid against the operand shapes, sums the VMEM footprint, and
re-traces the example for accumulator-dtype rules.  **The registry is
part of a kernel's interface**: a new kernel (or a new BlockSpec/grid
variant of an existing one — new index-map idiom, new prefetch table
layout) must add a registry example exercising it, and changing a
kernel's tiling means its example must still pass the checker at default
block sizes.

Telemetry hooks
---------------
Every STaMP linear — reference and fused — carries a ``site`` label
(``qkv``, ``wo``, ``gate_up``, ``wo_mlp``, ``moe``, ``in_proj``,
``out_proj``), and when `repro.models.lm.ServeConfig.quant_telemetry`
is on, records its transformed activation into
`repro.obs.quantstats` at trace time.  The stats are per-site scalar
reductions (clip/saturation counts, hi-token coverage, scale bounds)
computed in the SAME device program as the step — the fused kernels
themselves are untouched; the reductions read the kernel's *input*
activation, so telemetry never perturbs the integer path and adds zero
device dispatches.  The serving engines fold the scalars into their
metrics registry (``quant_*{site=…}``) and raise ``quant_clip_alert``
events past the configured threshold — see ``repro/obs/quantstats.py``
for the collection protocol (how records escape ``lax.scan``).
"""

from repro.kernels.ops import (  # noqa: F401
    haar_dwt_seq,
    int8_matmul,
    quantize_pack,
    stamp_decode_matmul,
    stamp_quant_dual_matmul,
    stamp_quant_matmul,
    stamp_quant_ragged_matmul,
    walsh_hadamard,
)
from repro.kernels.cache_attention import cache_decode_attention  # noqa: F401
from repro.kernels.paged_attention import (  # noqa: F401
    paged_decode_attention,
    paged_ragged_attention,
)
