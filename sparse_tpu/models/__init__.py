"""Benchmark/application model builders (the examples' compute cores).

Reference analog: the workload-construction halves of ``examples/pde.py``,
``examples/gmg.py``, ``examples/amg.py`` — kept importable here so the driver
entrypoint (``__graft_entry__.py``) and the example scripts
share one implementation.
"""

from .poisson import (  # noqa: F401
    cg_dia,
    cg_ell,
    cg_step_ell,
    laplacian_2d_csr,
    laplacian_2d_dia,
    laplacian_2d_ell,
    make_cg_step_dia,
    poisson_cg_state,
    poisson_cg_state_dia,
)
