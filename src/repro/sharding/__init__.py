from repro.sharding.ctx import ShardCtx
from repro.sharding.specs import (
    FLEET_AXIS,
    fleet_pspecs,
    fleet_shardings,
    param_pspecs,
    replicated_pspecs,
    train_state_pspecs,
)
