"""Compile-only checks: every Pallas kernel of the scan path compiles for a
described TPU v5e at deployment block counts.

Nothing runs; the TPU compiler that ships with jax is handed shapes only,
so Mosaic's tiling rules (a block's last two dims divisible by (8, 128)
or equal to the array's) and VMEM limits are enforced here, where the
interpret-mode parity tests cannot see them.  256 blocks (1,048,576
rows, 16 row groups) is a deployment-sized launch; 6, 12 and 96 are
ladder rungs (`ops.bucket_blocks`), 6 not a multiple of the kernels'
block group, so the padded tail compiles too.  Shapes are the lineitem
pages of `core/tpch.py`.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.agg_push import fused_agg_pallas, grouped_agg_pallas
from repro.kernels.bitunpack import bitunpack_pallas
from repro.kernels.bloom_probe import bloom_probe_pallas
from repro.kernels.delta_decode import delta_decode_pallas
from repro.kernels.dict_decode import dict_decode_batch_pallas, dict_decode_pallas
from repro.kernels.filter_compact import filter_compact_pallas
from repro.kernels.fused_scan import fused_scan_batch_pallas, fused_scan_pallas
from repro.kernels.rle_decode import rle_decode_pallas

u32, i32, f32, u8 = jnp.uint32, jnp.int32, jnp.float32, jnp.uint8


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# name -> (kernel with its static arguments bound, [(shape(nb), dtype)]);
# nb counts 4096-row pages, so 1024-row blocks number 4 * nb
KERNELS = {
    "bitunpack": (lambda p: bitunpack_pallas(p, 12, interpret=False),
                  [(lambda nb: (nb, 12, 128), u32)]),
    # l_shipdate: DICT over 2556 dates, k = 12
    "dict_decode": (lambda p, d: dict_decode_pallas(p, d, 12, interpret=False),
                    [(lambda nb: (nb, 12, 128), u32), (lambda nb: (2556,), i32)]),
    # l_discount: per-page DICT of 11 floats, k = 4
    "dict_decode_batch": (
        lambda p, d, s: dict_decode_batch_pallas(p, d, s, 4, interpret=False),
        [(lambda nb: (nb, 4, 128), u32), (lambda nb: (nb, 11), f32),
         (lambda nb: (nb, 1), i32)]),
    "delta_decode": (lambda p, b: delta_decode_pallas(p, b, 5, interpret=False),
                     [(lambda nb: (nb, 5, 128), u32), (lambda nb: (nb,), i32)]),
    "rle_decode": (lambda v, e: rle_decode_pallas(v, e, interpret=False),
                   [(lambda nb: (4 * nb, 128), f32), (lambda nb: (4 * nb, 128), i32)]),
    "fused_scan": (
        lambda p, lo, hi, d: fused_scan_pallas(p, 12, lo, hi, d, interpret=False),
        [(lambda nb: (nb, 12, 128), u32), (lambda nb: (), i32), (lambda nb: (), i32),
         (lambda nb: (2556,), i32)]),
    "fused_scan_batch": (
        lambda p, lohi: fused_scan_batch_pallas(p, 12, lohi, interpret=False),
        [(lambda nb: (nb, 12, 128), u32), (lambda nb: (nb, 2), i32)]),
    "filter_compact": (lambda v, m: filter_compact_pallas(v, m, interpret=False),
                       [(lambda nb: (4 * nb, 1024), f32), (lambda nb: (4 * nb, 1024), i32)]),
    # Q19's bloom: 2^15 bits
    "bloom_probe": (lambda k, b: bloom_probe_pallas(k, b, interpret=False),
                    [(lambda nb: (4 * nb, 1024), i32), (lambda nb: (1 << 15,), u8)]),
    # MAX_GROUPS groups: the widest pushed-down GROUP BY
    "grouped_agg": (lambda v, g, m: grouped_agg_pallas(v, g, m, 128, interpret=False),
                    [(lambda nb: (nb, 4096), f32), (lambda nb: (nb, 4096), i32),
                     (lambda nb: (nb, 4096), i32)]),
    "fused_agg": (lambda p, m: fused_agg_pallas(p, 6, m, interpret=False),
                  [(lambda nb: (nb, 6, 128), u32), (lambda nb: (nb, 4096), i32)]),
}


@pytest.mark.parametrize("nb", [256, 6, 12, 96])
@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name, nb):
    fn, args = KERNELS[name]
    shapes = [jax.ShapeDtypeStruct(shape(nb), dt, sharding=one_chip) for shape, dt in args]
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
    assert compiled.memory_analysis() is not None


# the engine's page split at lineitem's page lengths: (stacked output
# shape, dtype, L, pages) — a bitpack, an RLE float and a plain bucket of
# full 65,536-row groups, and a fused mask for the short last group alone
SPLITS = [((96, 32, 128), i32, 65536, 6), ((384, 1024), f32, 65536, 6),
          ((96, 4096), jnp.bool_, 36864, 1), ((96 * 65536,), f32, 65536, 96)]


@pytest.mark.parametrize("shape,dtype,L,pages", SPLITS)
def test_split_program_compiles_for_v5e(one_chip, shape, dtype, L, pages):
    from repro.core.engine import _split_program

    out = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((pages,), i32, sharding=one_chip)
    compiled = _split_program.lower(out, idx, idx, L=L).compile()
    assert len(compiled.out_info) == pages
    assert compiled.memory_analysis() is not None


# the engine's row-group mask at lineitem's row-group lengths: Q6's three
# comparisons on a full group, Q19's bloom leaf, `between` and sets on the
# short last group, and the trivial shapes (validity alone; a given mask)
MASKS = [("q6", 65536), ("q19", 36864), ("validity", 65536), ("given", 65536)]


@pytest.mark.parametrize("pred,L", MASKS)
def test_mask_program_compiles_for_v5e(one_chip, pred, L):
    from repro.core.engine import _GIVEN, _mask_program, _mask_shape, _operand
    from repro.core.plan import And, BloomProbe, Cmp, InSet
    from repro.core.queries import q6_plan

    def spec(shape, dtype, weak=False):
        return jax.ShapeDtypeStruct(shape, dtype, weak_type=weak, sharding=one_chip)

    n = spec((), i32, weak=True)
    if pred in ("validity", "given"):
        hits = (spec((L,), jnp.bool_),) if pred == "given" else ()
        shape = _GIVEN if pred == "given" else None
        compiled = _mask_program.lower((), (), hits, n, shape=shape, L=L).compile()
    else:
        expr = q6_plan(365).predicate if pred == "q6" else And((
            BloomProbe("l_partkey", name="q19"), Cmp("l_quantity", "between", (1, 30)),
            InSet("l_shipinstruct", (0,)), InSet("l_shipmode", (2, 5))))
        shape, columns, literals, probes = _mask_shape(expr)
        cols = tuple(spec((L,), f32 if c == "l_discount" else i32) for c in columns)
        lits = tuple(spec((), _operand(v).dtype, weak=True) for v in literals)
        hits = tuple(spec((L,), jnp.bool_) for _ in probes)
        compiled = _mask_program.lower(cols, lits, hits, n, shape=shape, L=L).compile()
    assert compiled.out_info.shape == (L,) and compiled.out_info.dtype == jnp.bool_
    assert compiled.memory_analysis() is not None
