"""Processing-in-memory layer: the `drim.jit` tracing front-end
(`frontend`), the staged compile -> lower -> run pipeline with one
engine registry (`compiler`), bulk-op scheduling over the simulated
DRIM fleet (`scheduler`), the (chips, banks) fleet mesh for sharded
simulation (`mesh`), fused dataflow graphs with resident intermediates
(`graph`, `bnn`), per-bank async command queues with MIMD graph
partitioning (`queue`), and the unified DRIM-vs-TPU placement Verdict
(`offload`).  The legacy `execute*`/`plan*` entry points remain as
deprecated shims over the pipeline."""
from .scheduler import (ENGINES, OP_ARITY, REF_OP, RESULT_ROWS, Schedule,
                        build_program, dispatch_waves, encoded_program,
                        execute, execute_oplist, expected_results,
                        fresh_encode_cache, plan_schedule, random_operands,
                        run_waves, run_waves_baseline, stage_rows, wave_fn)
from .mesh import (DEVICE_SPEC, STAGED_SPEC, WORDS_SPEC, fleet_mesh,
                   fleet_shape, shard_device, shard_staged, words_sharding)
from .graph import (BulkGraph, FusedProgram, FusedSchedule, GraphPartition,
                    QueueSegment, ValueRef, compile_graph, execute_graph,
                    graph_ref_results, partition_graph,
                    plan_graph_schedule)
from .queue import (QueueSchedule, bank_blocks, default_n_queues,
                    execute_partitioned, fused_queue_schedule,
                    plan_partitioned_schedule, plan_queued_schedule,
                    queue_mesh, run_waves_queued, stage_rows_queued,
                    uniform_queue_schedule)
from .frontend import (BitTensor, JittedFunction, TraceError,
                       TracedProgram, csa_reduce, full_add, jit, maj,
                       popcount, select, xnor)
from .compiler import (ENGINE_REGISTRY, PARTITIONERS, PASS_PIPELINE,
                       Compiled, Engine, EngineRegistry, Lowered, compile,
                       engines, get_engine, lower)
from . import verify
from .verify import (VerifyError, VerifyReport, verify_fused,
                     verify_lowered, verify_partition)
from .bnn import (bnn_dot_drim, bnn_dot_graph, bnn_dot_graph_carrysave,
                  bnn_dot_partitioned, counter_bits, decode_counts,
                  stage_bnn_planes)
from .offload import (FusedOffloadReport, OffloadReport, QueuedOffloadReport,
                      TpuCost, Verdict, VerdictRow, build_verdict, plan,
                      plan_fused, plan_model_payloads, plan_queued,
                      tpu_cost)
