"""``kernel_roofline`` for the kernels counted in ``harness/state_counts.py``:
the same reduction (its docstring says what is measured against what), on a
private copy of that reader bound to the second counts file.  Two files
because neither ``kernel_roofline.py`` nor ``kernel_counts.py`` may be
edited outside a ``benchmark`` PR; one merges them."""

from benchmarks.harness import state_counts
from benchmarks.harness.registry import BENCH_DIR, load_module

_reduction = load_module(BENCH_DIR / "readers" / "kernel_roofline.py")
_reduction.kernel_counts = state_counts


def read(ctx, *, kernel: str, module: str, calls: dict, counts: str):
    return _reduction.read(ctx, kernel=kernel, module=module, calls=calls,
                           counts=counts)
