"""``kernel_roofline`` for the kernels counted in ``harness/mamba1_counts.py``:
the same reduction (its docstring says what is measured against what), on a
private copy of that reader bound to that counts file — as
``state_kernel_roofline.py`` is to the second, and for its reason: neither
``kernel_roofline.py`` nor ``kernel_counts.py`` may be edited outside a
``benchmark`` PR; one merges them."""

from benchmarks.harness import mamba1_counts
from benchmarks.harness.registry import BENCH_DIR, load_module

_reduction = load_module(BENCH_DIR / "readers" / "kernel_roofline.py")
_reduction.kernel_counts = mamba1_counts


def read(ctx, *, kernel: str, module: str, calls: dict, counts: str):
    return _reduction.read(ctx, kernel=kernel, module=module, calls=calls,
                           counts=counts)
