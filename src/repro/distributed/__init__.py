from repro.distributed.sharding import (  # noqa: F401
    STRATEGIES,
    batch_pspecs,
    param_pspecs,
)
from repro.distributed.steps import make_train_fn  # noqa: F401
