"""cfree_expand_roofline: the cfree_expand kernel's share of its memory
roofline.

8 bytes per edge (the u and v int32 the kernel must write; the edge
index can be made inside the kernel, so it is not counted) over the
kernel's device time, over the chip's HBM bandwidth. The kernel does
integer hashing only and no integer peak of the chip is published, so
the bound is the bytes alone.

The kernel is found by its HLO: a ``tpu_custom_call`` whose operands are
the (rows, 128) int32 edge indices and the (4,) uint32 stream words. On
the chip each operand carries its tiled layout, as in
``s32[8388608,128]{1,0:T(8,128)} %multiply_add_fusion``.
"""
from bench import tracereduce

KERNEL = (r"custom-call\(s32\[\d+,128\]\S* %[^,]+, u32\[4\]\S* %[^)]+\), "
          r'custom_call_target="tpu_custom_call"')


def edge_bytes(edges: int) -> int:
    """Bytes the expansion of ``edges`` edges must write."""
    return 8 * edges


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    lo, hi = run.span
    ns = tracereduce.op_ns(run.trace, KERNEL, lo, hi)
    edges = sum(g.emitted for g in run.graphs)
    if not ns or not edges:
        return None
    return 100.0 * edge_bytes(edges) / (ns / 1e9) / run.peaks["hbm_bytes_per_s"]
