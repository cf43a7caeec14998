"""Cascade core — the paper's contribution as a composable library.

Public API:
    Fabric, TimingModel, generate_timing_model
    DFG and the pipelining passes (compute/broadcast/post-PnR, matching)
    CascadeCompiler / PassConfig / CompileResult
    DENSE_APPS / SPARSE_APPS benchmark suites
"""

from .apps import (ALL_APPS, CONTROL_APPS, DENSE_APPS, SPARSE_APPS,
                   AppSpec)
from .branch_delay import (MatchPlan, arrival_cycles_dfg, check_matched_dfg,
                           check_matched_netlist, match_dfg, match_netlist)
from .broadcast import broadcast_pipelining
from .cache import (DEFAULT_CACHE, DEFAULT_STAGE_CACHE, CompileCache,
                    DiskCache, StagePool, app_fingerprint, attach_disk_cache,
                    attach_stage_disk_cache, code_fingerprint, compile_key,
                    dfg_fingerprint, stage_key)
from .compiler import (BATCH_BACKENDS, CACHED_STAGES, BatchCompileError,
                       CascadeCompiler, CompileResult, MultiAppSpec,
                       PassConfig, compile_batch, compile_multi,
                       resident_config)
from .config import (PNR_BACKENDS, SIM_BACKENDS, STA_BACKENDS, cache_dir,
                     default_power_cap_mw, devices, disk_cache_enabled,
                     env_flag, env_float, env_int, force_host_device_count,
                     host_device_count, place_debug, pnr_backend,
                     sched_latency_weight, service_batch_window_s,
                     service_max_batch, worker_count)
from .dfg import DFG
from .explore import (ExploreSpec, FrontierPoint, ParetoFrontier,
                      evaluate_candidate, explore_frontier, pareto_prune)
from .flush import (SharedFlushReport, add_soft_flush,
                    flush_network_registers, remove_flush, shared_flush,
                    stateful_nodes)
from .interconnect import Fabric, Hop, Region, SubFabric, Tile
from .metrics import DesignMetrics, combine_metrics, evaluate_design
from .multi import (MultiAppResult, PackingError, RectRequest, aligned_cols,
                    assemble_pack, fabric_report, find_slot, fragmentation,
                    free_area, pack_rects, pack_regions, region_request,
                    repack_rects, sink_tiles_by_app, validate_regions)
from .netlist import Netlist, RoutedDesign, extract_netlist
from .passes import (CONFIG_FIELD_STAGE, DEFAULT_SCHEDULE, EXPLORE_SCHEDULE,
                     MULTI_POWER_CAPPED_SCHEDULE, MULTI_SCHEDULE,
                     NAMED_SCHEDULES, PASS_REGISTRY, POWER_CAPPED_SCHEDULE,
                     STAGE_OF_PASS, STAGE_ORDER, CompileContext, Pass,
                     PassPipeline, StageArtifact, register_pass,
                     resolve_schedule, stage_plan)
from .pipelining import collapse_reg_chains, compute_pipelining, find_reg_chains
from .place import PlaceParams, place, placement_stats
from .post_pnr import PostPnRParams, post_pnr_pipeline
from .power import EnergyParams, PowerReport, power_report
from .power_cap import (DesignCheckpoint, ParetoPoint, PowerCapResult,
                        evaluate_point, power_capped_pipeline)
from .route import RouteParams, route
from .schedule import Schedule, schedule_round2
from .sim import (clear_ref_memo, equivalent, output_latency, simulate,
                  simulate_sparse, sparse_equivalent)
from .sim_vec import (DenseProgram, SimLoweringError, SparseProgram,
                      lower_dense, lower_sparse, simulate_dense_vec,
                      simulate_sparse_vec)
from .sched import (POLICIES, FabricScheduler, Resident, ScheduleOutcome,
                    compare_policies, evaluate_static)
from .service import (CompileService, ServiceCancelled, ServiceClosed,
                      ServiceTicket, ServiceTimeout)
from .traffic import (AppTrafficStats, TrafficReport, TrafficTrace,
                      flush_downtime_cycles, periodic_trace, poisson_trace,
                      reconfig_cycles, replay, session_trace)
from .sta import STAReport, analyze, sdf_simulate_fmax
from .sta_vec import (IncrementalSTA, LoweredSTA, analyze_vec, lower_design)
from .timing_model import TECH_NS, TimingModel, generate_timing_model
from .unroll import max_copies, subfabric_for

__all__ = [
    "ALL_APPS", "CONTROL_APPS", "DENSE_APPS", "SPARSE_APPS", "AppSpec",
    "CascadeCompiler", "CompileResult", "PassConfig", "compile_batch",
    "BATCH_BACKENDS", "BatchCompileError",
    "MultiAppSpec", "MultiAppResult", "compile_multi", "PackingError",
    "Region", "SubFabric", "pack_regions", "region_request",
    "validate_regions", "sink_tiles_by_app", "fabric_report",
    "RectRequest", "aligned_cols", "find_slot", "pack_rects", "repack_rects",
    "free_area", "fragmentation", "assemble_pack", "resident_config",
    "CompileService", "ServiceTicket", "ServiceClosed", "ServiceCancelled",
    "ServiceTimeout", "StagePool",
    "FabricScheduler", "Resident", "ScheduleOutcome", "POLICIES",
    "evaluate_static", "compare_policies",
    "SharedFlushReport", "shared_flush", "flush_network_registers",
    "stateful_nodes", "combine_metrics", "MULTI_SCHEDULE",
    "CompileCache", "DiskCache", "DEFAULT_CACHE", "DEFAULT_STAGE_CACHE",
    "attach_disk_cache", "attach_stage_disk_cache",
    "compile_key", "stage_key", "app_fingerprint", "dfg_fingerprint",
    "code_fingerprint",
    "cache_dir", "default_power_cap_mw", "disk_cache_enabled", "env_flag",
    "env_float", "env_int", "place_debug", "worker_count",
    "service_batch_window_s", "service_max_batch", "sched_latency_weight",
    "PNR_BACKENDS", "pnr_backend", "SIM_BACKENDS", "STA_BACKENDS",
    "host_device_count", "force_host_device_count", "devices",
    "CompileContext", "Pass", "PassPipeline", "PASS_REGISTRY",
    "DEFAULT_SCHEDULE", "POWER_CAPPED_SCHEDULE", "EXPLORE_SCHEDULE",
    "MULTI_POWER_CAPPED_SCHEDULE",
    "NAMED_SCHEDULES", "resolve_schedule", "register_pass", "find_reg_chains",
    "STAGE_ORDER", "STAGE_OF_PASS", "CONFIG_FIELD_STAGE", "CACHED_STAGES",
    "StageArtifact", "stage_plan",
    "ExploreSpec", "FrontierPoint", "ParetoFrontier", "evaluate_candidate",
    "explore_frontier", "pareto_prune",
    "DesignMetrics", "evaluate_design",
    "DFG", "Fabric", "Hop", "Tile", "Netlist", "RoutedDesign",
    "TimingModel", "TECH_NS", "generate_timing_model",
    "analyze", "sdf_simulate_fmax", "STAReport",
    "LoweredSTA", "IncrementalSTA", "lower_design", "analyze_vec",
    "match_dfg", "match_netlist", "MatchPlan",
    "check_matched_dfg", "check_matched_netlist",
    "arrival_cycles_dfg", "compute_pipelining", "collapse_reg_chains",
    "broadcast_pipelining", "post_pnr_pipeline", "PostPnRParams",
    "place", "PlaceParams", "placement_stats", "route", "RouteParams",
    "extract_netlist", "Schedule", "schedule_round2",
    "EnergyParams", "PowerReport", "power_report",
    "DesignCheckpoint", "ParetoPoint", "PowerCapResult", "evaluate_point",
    "power_capped_pipeline",
    "add_soft_flush", "remove_flush",
    "simulate", "simulate_sparse", "equivalent", "sparse_equivalent",
    "output_latency", "clear_ref_memo",
    "SimLoweringError", "DenseProgram", "SparseProgram", "lower_dense",
    "lower_sparse", "simulate_dense_vec", "simulate_sparse_vec",
    "TrafficTrace", "TrafficReport", "AppTrafficStats", "replay",
    "periodic_trace", "poisson_trace", "session_trace",
    "flush_downtime_cycles", "reconfig_cycles",
    "max_copies", "subfabric_for",
]
