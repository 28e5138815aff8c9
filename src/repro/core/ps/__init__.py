from repro.core.ps.sync import (  # noqa: F401
    PSConfig, PSState, make_worker_mesh, init_state,
    replicate_for_workers, worker_mean,
)
from repro.core.ps import simulator, trainer  # noqa: F401
