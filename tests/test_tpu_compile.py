"""The main-path Pallas kernels compile for a described TPU v5e.

Nothing runs: each kernel is lowered and compiled by the TPU compiler
for one chip of a described `v5e:2x2` topology at the headline widths
(d = rot = 96, 1,024 lists x 1,024 slots, chunk 128, k = 10), and the
compiled program must hold the Mosaic kernel (`tpu_custom_call`). This
catches what interpret mode cannot: primitives Mosaic does not lower,
unaligned blocks, VMEM overruns.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from raft_tpu.ops import fused_scan as fs
from raft_tpu.ops.pairwise_pallas import pairwise_tiled
from raft_tpu.ops.select_counting import counting_select_min

D, N_LISTS, L, CHUNK, K, NCB = 96, 1024, 1024, 128, 10, 256


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip are written to the persistent cache
    # but can never be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


f32, i32, u32 = jnp.float32, jnp.int32, jnp.uint32
LOF = ((NCB,), i32)
BASE = ((N_LISTS, 1, L), f32)

#: kernel -> (call, argument shapes)
CASES = {
    "fused_topk": (
        lambda x, y: fs.fused_topk(x, y, K),
        [((128, D), f32), ((1_000_000, D), f32)]),
    "fused_list_topk_bf16": (
        lambda *a: fs.fused_list_topk(*a, K),
        [LOF, ((NCB, CHUNK, D), f32), ((N_LISTS, L, D), jnp.bfloat16),
         BASE]),
    "fused_list_topk_int8": (
        lambda *a: fs.fused_list_topk_int8(*a, K),
        [LOF, ((NCB, CHUNK, D), jnp.int8), ((N_LISTS, L, D), jnp.int8),
         BASE, ((NCB, CHUNK, 1), f32)]),
    "pairwise_tiled_l1": (
        lambda x, y: pairwise_tiled(x, y, "l1"),
        [((1024, D), f32), ((4096, D), f32)]),
    "counting_select_min": (
        lambda v: counting_select_min(v, K),
        [((128, 131072), f32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


#: the batch cell's list-major widths: 1,024 lists x 2,944 slots, 8
#: probes, k = 40, over its 4,096- and 2,048-query slices
LM_SLOTS, LM_PROBES, LM_K = 2944, 8, 40
#: slice -> the rule's chunk (dup 32 and 16, both 32), superblocks, and
#: chunks a superblock: 2,048 and 1,536 chunks
LM_SLICES = {4096: (32, 2, 1024), 2048: (32, 2, 768)}


@pytest.fixture(scope="module", params=sorted(LM_SLICES, reverse=True))
def listmajor_hlo(one_chip, request):
    """The IVF-PQ list-major search program of one of the batch cell's
    slices (rot 96, int8 store, bf16 trim), compiled for one v5e, with
    the slice's (nq, chunk, nsuper, sb)."""
    from raft_tpu.distance.distance_types import DistanceType
    from raft_tpu.neighbors.ivf_pq import _search_impl_recon8_listmajor
    from raft_tpu.neighbors.probe_invert import (
        chunk_count,
        list_chunk,
        superblocks,
    )

    nq = request.param
    chunk, nsuper, sb = LM_SLICES[nq]
    assert list_chunk(nq, LM_PROBES, N_LISTS) == chunk
    assert superblocks(chunk_count(nq, LM_PROBES, N_LISTS, chunk), chunk,
                       LM_SLOTS) == (nsuper, sb)
    shapes = [((nq, D), f32), ((D, D), f32), ((N_LISTS, D), f32),
              ((N_LISTS, LM_SLOTS, D), jnp.int8), ((D,), f32),
              ((N_LISTS, LM_SLOTS), f32), ((N_LISTS, LM_SLOTS), i32)]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    txt = _search_impl_recon8_listmajor.lower(
        *args, k=LM_K, n_probes=LM_PROBES, metric=DistanceType.L2Expanded,
        trim_bf16=True).compile().as_text()
    return txt, (nq, chunk, nsuper, sb)


def test_listmajor_trim_lowers_to_topk(listmajor_hlo):
    """At the batch cell's widths the list-major trim compiles to the
    TPU's partial-select TopK over the 2,944-slot rows, one a superblock
    of sb x chunk rows, each in the rows-on-lanes layout ({0,1}) it gets
    outside a loop body at chunks of 32 and more, and no sort runs over
    that axis."""
    import re

    txt, (_, chunk, nsuper, sb) = listmajor_hlo
    defs = {name: (dims, layout) for name, dims, layout in
            re.findall(r"(%[\w.-]+) = \w+\[([\d,]*)\]\{([\d,]*)", txt)}
    operands = re.findall(
        r"custom-call\((%[\w.-]+)\), custom_call_target=\"TopK\"", txt)
    trims = [defs[o] for o in operands
             if defs[o][0].split(",")[-1] == str(LM_SLOTS)]
    assert trims and all(layout == "0,1" for _, layout in trims), trims
    assert [dims for dims, _ in trims] == [f"{sb * chunk},{LM_SLOTS}"] * nsuper
    assert not re.search(rf"\[[\d,]*\b{LM_SLOTS}\b[\d,]*\][^\n]* sort\(",
                         txt)


def test_listmajor_ids_looked_up_after_merge(listmajor_hlo):
    """The row ids are looked up for the merged top-k only: no gather
    makes an output of one entry per trimmed slot of a superblock's chunk
    rows (sb x chunk x k) but the regroup's two, and the widest int32
    gather is the regroup of the nq x n_probes x k candidate slots."""
    import math
    import re

    txt, (nq, chunk, _, sb) = listmajor_hlo
    sizes = [(dt, math.prod(int(d) for d in dims.split(",") if d))
             for dt, dims in re.findall(
                 r"= (\w+)\[([\d,]*)\]\{[^}]*\} gather\(", txt)]
    assert sizes
    # at the 4,096-query slice a superblock's sb x chunk rows number its
    # nq x n_probes pairs, so the regroup's gathers of candidate values
    # and slots have that size too; a lookup a superblock would add more
    assert sum(n == sb * chunk * LM_K for _, n in sizes) <= 2
    assert max(n for dt, n in sizes if dt == "s32") <= nq * LM_PROBES * LM_K


#: wiki1m-rabitq's widths: 768-d (24 code words), 4,096 lists, 4-bit
#: query planes, 16 probes, a 320-row scan over a 10,000-query call;
#: the longest list is what the coarse k-means gives the cell's data
#: (the generator's 1,024 blobs: whole blobs a list): 2,016 slots on the
#: cell's corpus, 3,008 on the longest of the five read on a v5e
RQ_D, RQ_LISTS, PROBES, KK = 768, 4096, 16, 320


@pytest.mark.parametrize("slots", [2016, 3008])
def test_rabitq_search_compiles_at_cell_widths(one_chip, slots):
    """The IVF-RaBitQ search program compiles at the benchmark cell's
    widths: the XLA bit-plane scan over a 10,000-query call."""
    from raft_tpu.distance.distance_types import DistanceType
    from raft_tpu.neighbors import ivf_rabitq

    words = RQ_D // 32
    shapes = [((10_000, RQ_D), f32), ((RQ_D, RQ_D), f32),
              ((RQ_LISTS, RQ_D), f32), ((RQ_LISTS, slots, words), u32),
              ((RQ_LISTS, slots, 2), f32), ((RQ_LISTS, slots), i32)]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    txt = ivf_rabitq._search_impl_rabitq.lower(
        *args, k=KK, n_probes=PROBES, metric=DistanceType.L2Expanded,
        query_bits=4).compile().as_text()
    assert "tpu_custom_call" not in txt
