"""Each file of this suite starts with no program left behind by the
files its worker ran before it.

A toy cell is traced in the test's own process, and the profile's
metadata lists programs that process still holds.  One that shows an
``hvd_*`` scope and no pass — a Pallas kernel called alone by
``tests/test_pallas_attention.py``, say — has an ``op_name`` that
``benchmark/scopes.elect`` cannot place (``ValueError`` in
``STAGES.index``), so whether ``test_benchmark_scopes.py`` passed used to
depend on which files shared its worker.  No cell's run meets such a
program: a run is a process of its own.
"""

import pytest


@pytest.fixture(scope="module", autouse=True)
def _no_program_left_behind():
    import jax

    jax.clear_caches()
    yield
