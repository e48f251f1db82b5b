"""Elementwise binary ops (the reference's Element op).

ElementType ADD/MUL (gnn.h:88-91; op_kernel element_kernel.cu:19-39).  ADD is
what the residual path uses (gnn.cc:86-90).  The reference's MUL backward is
unimplemented (`assert(false)`, element_kernel.cu:102-104); ours comes from
autodiff, so MUL is fully supported here.
"""


def add(a, b, wa=None, wb=None):
    """``a + b``; with scalar weights, ``wa * a + wb * b`` (GCNII's initial
    residual and identity mapping; a weight left out is 1)."""
    if wa is not None:
        a = a * wa
    if wb is not None:
        b = b * wb
    return a + b


def mul(a, b):
    return a * b
