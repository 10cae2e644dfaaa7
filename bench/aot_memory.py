"""Size a cell without the chip: compile its largest unified-step bucket
for a described v5e and print the compiled program's memory, with the
int4 weights the engine keeps beside it.

    JAX_PLATFORMS=cpu python bench/aot_memory.py <cell>

Nothing runs; the TPU compiler only reports what the step would need.
The engine returns the KV pools as a new copy each step (they are not
donated), so outputs count in full.
"""

from __future__ import annotations

import os
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import harness
    from repro.core.stamp import StampConfig
    from repro.kernels import decode_matmul, ops
    from repro.models import lm
    from repro.serving import paged_kvcache as PKV
    from repro.serving.kvcache import KVCacheConfig

    # the kernels pick interpret mode from the host's backend; compile
    # them for the described chip instead
    ops.default_interpret = decode_matmul.default_interpret = lambda: False
    jax.config.update("jax_enable_compilation_cache", False)
    name = (argv or sys.argv[1:])[0]
    cell = harness.cell(harness.load_json(harness.ROOT / "BENCHMARK.json"),
                        name)
    c, mix = cell.config, cell.mix
    cfg = harness.load_module(
        BENCH / "families" / f"{c['family']}.py").model_config(c)
    s, kvs = c["serving"]["stamp"], c["serving"]["kv_cache"]
    stamp = StampConfig(seq_transform=s["transform"], levels=s["levels"],
                        num_hi_tokens=s["num_hi"], hi_bits=s["hi_bits"],
                        lo_bits=s["lo_bits"],
                        skip_first_token=s["skip_first_token"],
                        execution="fused")
    kv = KVCacheConfig(quantized=True, num_hi=kvs["num_hi"],
                       hi_bits=kvs["hi_bits"], lo_bits=kvs["lo_bits"])
    slots, chunk, npf = mix["slots"], mix["prefill_chunk"], \
        mix["max_prefills"]
    bs = np.gcd(16, kv.num_hi)
    lo_per = -(-(mix["max_seq"] - kv.num_hi) // bs)
    hi_per = kv.num_hi // bs
    pcfg = PKV.PagedCacheConfig(
        block_size=int(bs), num_lo_blocks=slots * lo_per + 1,
        num_hi_blocks=slots * hi_per + 1, max_blocks_per_seq=lo_per,
        quant=kv)
    serve = lm.ServeConfig(stamp=stamp, kv=kv, weight_bits=4, paged=pcfg,
                           fused_decode_matmul=True)
    packed = jax.eval_shape(lambda k: lm.init_params(k, cfg, weight_bits=4),
                            jax.random.PRNGKey(0))
    prepared = jax.eval_shape(lambda p: lm.prepare_fused_weights(p, stamp),
                              packed)
    pools = jax.eval_shape(lambda: lm.init_paged_cache(cfg, pcfg,
                                                       num_slots=slots))
    dev = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def sds(t):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=dev), t)

    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    i32, b1 = jnp.int32, jnp.bool_
    n = npf * chunk + slots
    args = (sds(prepared), sds(pools), arr(i32, npf, chunk), arr(i32, npf),
            arr(i32, npf), arr(b1, npf), arr(i32, npf), arr(i32, npf),
            arr(i32, slots), arr(i32, slots), arr(b1, slots),
            arr(i32, npf + slots, hi_per), arr(i32, npf + slots, lo_per),
            arr(i32, n), arr(i32, n), arr(b1, n))
    comp = jax.jit(lambda *a: lm.paged_unified_step(*a, cfg, serve)).lower(
        *args).compile()
    m = comp.memory_analysis()

    def nbytes(t):
        return sum(int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
                   for a in jax.tree.leaves(t))

    kept = nbytes({k: v for k, v in packed.items()
                   if k not in ("embed", "head", "final_norm")})
    total = m.argument_size_in_bytes + m.output_size_in_bytes \
        + m.temp_size_in_bytes + kept
    print(f"{name}: arguments {m.argument_size_in_bytes} B (weights "
          f"{nbytes(prepared)}, KV pools {nbytes(pools)}), outputs "
          f"{m.output_size_in_bytes} B, temporaries {m.temp_size_in_bytes} B,"
          f" int4 weights kept {kept} B; total {total} B; Pallas kernels "
          f"{'tpu_custom_call' in comp.as_text()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
