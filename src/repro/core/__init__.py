"""Core: the paper's contribution -- consensus-based distributed optimization
with explicit communication/computation tradeoff control."""

from repro.core.graphs import (CommGraph, GraphSequence, build_graph,
                               complete_graph, expander_sequence,
                               hypercube_graph, kregular_expander, lambda2,
                               random_regular_expander, ring_graph,
                               spectral_gap, torus_graph)
from repro.core.schedules import (CommSchedule, EveryIteration,
                                  IncreasinglySparse, Periodic,
                                  PiecewisePeriodic, c1_constant,
                                  ch_constant, cp_constant, make_schedule,
                                  optimal_stepsize_A)
from repro.core.tradeoff import (TPU_V5E, HardwareSpec, derive_r_from_roofline,
                                 ew_alpha, ew_update, h_opt, h_opt_int,
                                 iteration_cost, lambda2_fast, measure_r,
                                 n_opt_complete, predict_speedup,
                                 time_to_accuracy)
from repro.core.consensus import (disagreement, mix_collective, mix_dense,
                                  mix_stale, stale_combine, stale_combine_batch,
                                  tree_mix_collective, tree_mix_dense)
from repro.core.dda import DDASimulator, SimTrace, stepsize_sqrt
