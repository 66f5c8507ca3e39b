"""FL runtime: scan-based async simulation engine + mega-scale distributed step."""
from .engine import (MatrixResult, RoundTrace, SimConfig, SimResult,
                     build_chunk_sim, build_scan_sim, grant_forced_bandwidth,
                     init_carry, make_runner, resolve_data_path,
                     run_scenario_matrix, run_seed_matrix,
                     run_simulation_scan, stack_round_batches)
from .faults import (FaultConfig, FaultMatrixResult, FaultOutcome,
                     FaultParams, FaultState, GuardConfig, apply_faults,
                     corrupt_deltas, fault_key, init_fault_state,
                     run_fault_matrix, scale_params)
from .resume import completed_segments, run_resumable, segment_bounds
from .schemes import (SchemeMatrixResult, SchemeSpec, default_scheme_panel,
                      run_scheme_matrix)
from .simulator import run_simulation, run_simulation_legacy
from .sparse import (ParticipationTrace, build_participation_program,
                     build_sparse_train_program, make_sparse_runner,
                     resolve_participation, train_trace_count)
from .state import (AggParams, AggregatorConfig, FLState,
                    broadcast_to_participants, finite_rows, guard_weights,
                    init_fl_state, masked_aggregate, pseudo_gradients,
                    round_weights, scheme_weights, staleness_scale,
                    update_norms)

__all__ = ["SimConfig", "SimResult", "run_simulation",
           "run_simulation_legacy", "run_simulation_scan", "build_scan_sim",
           "build_chunk_sim", "make_runner", "resolve_data_path",
           "run_seed_matrix", "run_scenario_matrix", "stack_round_batches",
           "grant_forced_bandwidth", "MatrixResult", "RoundTrace", "FLState",
           "init_fl_state", "init_carry", "masked_aggregate",
           "round_weights", "pseudo_gradients",
           "broadcast_to_participants", "make_sparse_runner",
           "resolve_participation", "build_participation_program",
           "build_sparse_train_program", "ParticipationTrace",
           "train_trace_count",
           # robustness layer (docs/robustness.md)
           "FaultConfig", "FaultParams", "FaultState", "FaultOutcome",
           "GuardConfig", "FaultMatrixResult", "apply_faults",
           "corrupt_deltas", "fault_key", "init_fault_state", "scale_params",
           "run_fault_matrix", "finite_rows", "update_norms",
           "guard_weights",
           "run_resumable", "segment_bounds", "completed_segments",
           # scheme matrix (docs/schemes.md)
           "AggParams", "AggregatorConfig", "SchemeMatrixResult",
           "SchemeSpec", "default_scheme_panel", "run_scheme_matrix",
           "scheme_weights", "staleness_scale"]
