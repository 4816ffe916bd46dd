"""The benchmark's tests run on the host CPU, with the Pallas kernels in
interpret mode, at sizes far below a cell's."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _restore_mesh():
    """A service built from a spec installs its serving mesh process-wide
    (`repro.distributed.context`); put back what was there, so that the
    tests that share this process see none of it."""
    from repro.distributed import context

    axes, mesh = context.get(), context.get_mesh()
    yield
    if context.get() != axes or context.get_mesh() is not mesh:
        if axes is None:
            context.clear()
        else:
            context.set_mesh_axes(axes.dp, axes.model, mesh)
