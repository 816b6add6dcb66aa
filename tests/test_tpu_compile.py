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
