"""The paper's primary contribution: the ExaDigiT/RAPS-style datacenter
digital twin — trace replay, rescheduling, power/cooling/carbon chain,
network congestion, failures — as a pure-JAX vectorized simulator.
"""

from repro.core.faults import (
    LVL_DRAIN,
    LVL_EVICT,
    LVL_GATE,
    LVL_NORMAL,
    LVL_THROTTLE,
    apply_faults,
    effective_level,
    next_fault_event,
)
from repro.core.fleet import (
    fleet_summary,
    policy_scenario_grid,
    run_fleet,
    shard_fleet,
)
from repro.core.placement import (
    PLACE_IDS,
    PLACEMENTS,
    Policy,
    make_policy,
    policy_grid,
    stack_policies,
)
from repro.core.schedulers import SCHEDULERS, SELECT_IDS
from repro.core.serving import (
    apply_serving,
    next_serving_event,
    retry_backoff,
    serving_crossing_horizon,
    serving_flow,
    serving_power,
    serving_trigger,
)
from repro.core.thermal import (
    cooling_cop,
    node_trip_ok,
    rack_throttle,
    rack_thermal_update,
    supply_temp,
    thermal_alpha,
    thermal_crossing_horizon,
)
from repro.core.sim import (
    StepOut,
    TelemetrySummary,
    make_macro_step,
    make_step,
    quiet_horizon,
    run_episode,
    run_segment,
    summary,
    summary_columns,
    telem_finalize,
    telem_zero,
)
from repro.core.state import (
    DONE,
    EMPTY,
    FAILED,
    QUEUED,
    RUNNING,
    SimState,
    Statics,
    Stream,
    Trace,
    build_statics,
    init_state,
    load_jobs,
    trace_records,
)
