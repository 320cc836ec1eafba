"""CLI (ref: blades/train.py): ``python -m blades_tpu.train file <yaml>`` /
``run <ALGO>`` — argparse instead of Typer (not in this image), same
command surface: experiment files with grid_search, or a one-off run with
inline overrides."""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="blades_tpu.train",
        description="TPU-native Byzantine-robust FL training "
        "(ref CLI surface: blades/train.py:129-307)",
    )
    sub = parser.add_subparsers(dest="cmd", required=True)

    # Flags shared by BOTH subcommands, defined once (parents=): the run
    # subcommand silently ignoring --trace was exactly the drift that
    # copy-pasted flag blocks invite.
    common = argparse.ArgumentParser(add_help=False)
    common.add_argument("--storage-path", default="~/blades_tpu_results")
    common.add_argument("--trace", default=None, metavar="DIR",
                        help="capture a jax profiler trace into DIR "
                        "(the reference's --trace flag is dead code; this "
                        "one works, on both subcommands)")
    common.add_argument("--trace-dir", default=None, metavar="DIR",
                        help="span tracing (obs/trace.py): export each "
                        "trial's host span tree (trial -> round -> phase, "
                        "round provenance stamped as args) as "
                        "Chrome/Perfetto trace JSON into DIR; composes "
                        "with --trace — armed spans annotate the profiler "
                        "capture so device work nests inside host spans")
    common.add_argument("--watchdog", action="store_true",
                        help="arm the anomaly watchdog (obs/watchdog.py): "
                        "schema-driven rules (NaN aggregate, update-norm "
                        "spike, detection-FPR collapse, round-time "
                        "regression) over the already-fetched rows; "
                        "events land in metrics rows as watchdog_events "
                        "and trigger the flight-recorder dump")
    common.add_argument("--watchdog-rules", default=None, metavar="JSON",
                        help="replace the watchdog's built-in rule table "
                        "with a JSON list of rule specs, e.g. "
                        "'[{\"name\": \"acc\", \"kind\": \"collapse\", "
                        "\"field\": \"test_acc\"}]' (kinds: nonfinite, "
                        "spike, ceiling, collapse, round_time_regression); "
                        "implies --watchdog; validated fail-fast before "
                        "any trial starts (see README \"Control plane\")")
    common.add_argument("--flightrec-rounds", type=int, default=16,
                        metavar="K",
                        help="flight recorder (obs/flightrec.py): ring of "
                        "the last K round digests per trial, dumped "
                        "atomically to <trial>/flightrec.json on NaN "
                        "aggregate / crash / preemption (replay with "
                        "python -m tools.replay_round); 0 disables")
    common.add_argument("--metrics-csv", action="store_true",
                        help="also write <trial>/metrics.csv next to the "
                        "canonical metrics.jsonl stream")
    common.add_argument("--no-cost-analysis", action="store_true",
                        help="skip the per-trial XLA cost analysis (it "
                        "recompiles the training dispatch once — expensive "
                        "for ResNet-scale models on CPU)")
    common.add_argument("--autotune", nargs="?", const="on", default=None,
                        choices=("on", "reassociating"),
                        help="execution autotuner (perf/autotune.py): "
                        "enumerate the legal execution plans, time them on "
                        "TPU (deterministic ranked heuristic on CPU), cache "
                        "the winner.  Bare --autotune tunes the numerics-"
                        "preserving default tier (bit-identical to the "
                        "untuned path); '--autotune reassociating' also "
                        "offers dense<->streamed<->packed switches and the "
                        "stats-MXU finish (documented float tolerances).  "
                        "Explicit knobs (--client-packing, execution, "
                        "d_chunk) are never overridden — "
                        "the tuner only resolves what was left at 'auto'; "
                        "see README \"Execution autotuner\"")
    common.add_argument("--plan-cache-dir", default=None, metavar="DIR",
                        help="persistent plan-cache location for --autotune "
                        "(default $BLADES_TPU_PLAN_CACHE_DIR or "
                        "~/.cache/blades_tpu/plans); inspect with "
                        "python -m tools.show_plan")
    common.add_argument("-v", "--verbose", action="count", default=1)

    p_file = sub.add_parser("file", parents=[common],
                            help="run experiments from a YAML grid file")
    p_file.add_argument("experiment_file")
    p_file.add_argument("--checkpoint-freq", type=int, default=0)
    p_file.add_argument("--checkpoint-at-end", action="store_true")
    p_file.add_argument("--checkpoint-keep-num", type=int, default=None,
                        help="keep only the N best periodic checkpoints "
                        "(ref: blades/train.py:175-180)")
    p_file.add_argument("--checkpoint-score-attr", default="training_iteration",
                        help="result key ranking checkpoints for --checkpoint-"
                        "keep-num (e.g. test_acc)")
    p_file.add_argument("--resume", action="store_true",
                        help="skip finished trials, restore in-flight ones "
                        "from their latest checkpoint (ref: blades/"
                        "train.py:154,228)")
    p_file.add_argument("--max-rounds", type=int, default=None,
                        help="override every experiment's training_iteration")
    p_file.add_argument("--max-failures", type=int, default=0,
                        help="retry a crashed trial from its latest "
                        "checkpoint up to N times, then mark it failed and "
                        "keep sweeping (Tune's trial fault tolerance); "
                        "restarts back off exponentially with deterministic "
                        "jitter")
    p_file.add_argument("--preempt-after", type=int, default=None,
                        metavar="N",
                        help="chaos test hook: raise a SimulatedPreemption "
                        "once, the first time a trial finishes round N "
                        "(between the result write and the checkpoint "
                        "save), exercising kill-and-resume end-to-end; "
                        "combine with --max-failures or --resume")
    p_file.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                        help="multi-host bring-up via jax.distributed — the "
                        "TPU-native replacement for the reference's NCCL "
                        "init_process_group (ref: fllib/communication/"
                        "communicator.py:148); also honours "
                        "JAX_COORDINATOR_ADDRESS")
    p_file.add_argument("--num-processes", type=int, default=None)
    p_file.add_argument("--process-id", type=int, default=None)
    p_file.add_argument("--no-lanes", action="store_true",
                        help="disable vmapped lane execution of shape-"
                        "compatible trial groups (seed/lr/eps/scale grids); "
                        "every trial then runs sequentially")

    p_run = sub.add_parser("run", parents=[common],
                           help="run one algorithm with overrides")
    p_run.add_argument("algo", help="FEDAVG or FEDAVG_DP")
    p_run.add_argument("--config-json", default="{}",
                       help='flat/nested config overrides as JSON, e.g. '
                       '\'{"dataset_config": {"type": "mnist"}}\' or a '
                       'compressed-uplink run \'{"codec_config": '
                       '{"type": "topk", "topk_ratio": 0.01}}\' '
                       '(see README "Communication codecs")')
    p_run.add_argument("--rounds", type=int, default=100)
    p_run.add_argument("--client-packing", default=None, metavar="P",
                       help="client lane-packing on the dense round: "
                       "'auto' (pack 2 clients per grouped-kernel lane "
                       "iff the width/divisibility heuristic passes, loud "
                       "fallback otherwise), an int P>=2 to force, 'off' "
                       "(default; see README \"Client packing\")")
    p_run.add_argument("--execution", default=None,
                       choices=("auto", "dense", "streamed", "dsharded",
                                "async", "hier", "gossip"),
                       help="execution path override; 'async' runs the "
                       "buffered-async mode (blades_tpu/arrivals): a "
                       "deterministic Poisson arrival process, clients "
                       "computing against the version they last pulled, "
                       "staleness-weighted robust aggregation every K "
                       "buffered arrivals (see README \"Async buffered "
                       "execution\"); 'hier' runs the pod-scale "
                       "hierarchical round (see README \"Pod-scale "
                       "federation\"); 'gossip' runs the decentralized "
                       "per-node round over a peer graph (see README "
                       "\"Decentralized gossip federation\")")
    p_run.add_argument("--mesh-shape", default=None, metavar="CxD",
                       help="2-D (clients, d) device mesh for multi-chip "
                       "runs, e.g. '4x2'; must tile num_devices exactly "
                       "(parallel/mesh.py)")
    p_run.add_argument("--preagg", default=None,
                       choices=("bucket", "nnm"),
                       help="hierarchical per-shard pre-aggregation "
                       "flavor for --execution hier (ops/preagg.py): "
                       "'bucket' averages disjoint buckets, 'nnm' mixes "
                       "each update with its nearest neighbors")
    p_run.add_argument("--bucket-size", type=int, default=None, metavar="B",
                       help="pre-aggregation bucket size for --execution "
                       "hier; 1 (default) is the identity pre-agg — "
                       "bit-identical to the single-chip dense round")
    p_run.add_argument("--arrivals-json", default=None, metavar="SPEC",
                       help="async arrival spec as JSON for "
                       "--execution async, e.g. '{\"rate\": 0.25, "
                       "\"agg_every\": 16, \"weight_schedule\": "
                       "\"polynomial\"}' (AsyncSpec knobs; seed defaults "
                       "to the trial seed)")
    p_run.add_argument("--state-store", default=None,
                       choices=("resident", "host", "disk"),
                       help="out-of-core per-client state backend "
                       "(blades_tpu/state): where off-cohort optimizer/"
                       "EF-residual rows live; 'host'/'disk' require "
                       "--window (see README \"Out-of-core client "
                       "state\")")
    p_run.add_argument("--data-store", default=None,
                       choices=("resident", "memmap"),
                       help="out-of-core training-data backend "
                       "(blades_tpu/data/store.py): 'memmap' spills the "
                       "per-client partition to CRC'd disk shards and "
                       "gathers only each cohort's rows; needs --window "
                       "or async × out-of-core --state-store (see README "
                       "\"Out-of-core training data\")")
    p_run.add_argument("--data-dir", default=None, metavar="DIR",
                       help="live shard directory for --data-store "
                       "memmap (default: a private temp dir); a matching "
                       "manifest is reused on resume, a mismatch "
                       "rebuilds from source")
    p_run.add_argument("--topology", default=None,
                       choices=("ring", "torus", "kregular", "erdos",
                                "complete"),
                       help="peer graph family for --execution gossip "
                       "(blades_tpu/topology); 'complete' with Mean is "
                       "bit-identical to the centralized dense round")
    p_run.add_argument("--mixing", default=None,
                       choices=("metropolis", "uniform"),
                       help="doubly-stochastic mixing scheme for "
                       "--execution gossip; Metropolis–Hastings weights "
                       "by default")
    p_run.add_argument("--graph-seed", type=int, default=None,
                       metavar="S",
                       help="seed for the random graph families "
                       "(--topology erdos); part of the run provenance "
                       "so two processes build the same graph")
    p_run.add_argument("--window", type=int, default=None, metavar="W",
                       help="participation window: clients sampled into "
                       "each round's cohort (0 = stateless clients, the "
                       "degenerate case); only the cohort's state rows "
                       "are device-resident under a host/disk store")

    args = parser.parse_args(argv)

    # --watchdog-rules: parse + validate BEFORE building experiments so a
    # typo'd rule spec dies here, not 40 minutes into a sweep.  The parsed
    # list rides the same `watchdog=` channel (a sequence arms the
    # watchdog with exactly these rules; a bool arms the defaults).
    watchdog = args.watchdog
    if args.watchdog_rules is not None:
        try:
            specs = json.loads(args.watchdog_rules)
        except json.JSONDecodeError as exc:
            parser.error(f"--watchdog-rules is not valid JSON: {exc}")
        if not isinstance(specs, list):
            parser.error("--watchdog-rules must be a JSON list of rule "
                         f"specs, got {type(specs).__name__}")
        from blades_tpu.obs.watchdog import rules_from_config

        try:
            rules_from_config(specs)  # fail-fast validation only
        except (ValueError, TypeError) as exc:
            parser.error(f"--watchdog-rules: {exc}")
        watchdog = specs

    from blades_tpu.tune import load_experiments_from_file, run_experiments

    if args.cmd == "file":
        # Must run before any other jax call (see init_distributed); no-op
        # when neither --coordinator nor JAX_COORDINATOR_ADDRESS is set.
        from blades_tpu.parallel import init_distributed

        init_distributed(args.coordinator, args.num_processes, args.process_id)
        experiments = load_experiments_from_file(args.experiment_file)

        def _run():
            return run_experiments(
                experiments,
                storage_path=args.storage_path,
                verbose=args.verbose,
                checkpoint_freq=args.checkpoint_freq,
                checkpoint_at_end=args.checkpoint_at_end,
                checkpoint_keep_num=args.checkpoint_keep_num,
                checkpoint_score_attr=args.checkpoint_score_attr,
                resume=args.resume,
                max_rounds_override=args.max_rounds,
                max_failures=args.max_failures,
                preempt_after=args.preempt_after,
                lanes=not args.no_lanes,
                metrics_csv=args.metrics_csv,
                cost_analysis=not args.no_cost_analysis,
                autotune=args.autotune,
                plan_cache_dir=args.plan_cache_dir,
                trace_dir=args.trace_dir,
                watchdog=watchdog,
                flightrec_rounds=args.flightrec_rounds,
            )

    else:
        run_config = json.loads(args.config_json)
        if args.client_packing is not None:
            cp = args.client_packing
            run_config["client_packing"] = (cp if cp in ("auto", "off")
                                            else int(cp))
        if args.execution is not None:
            run_config["execution"] = args.execution
        if args.mesh_shape is not None:
            try:
                c, dd = args.mesh_shape.lower().split("x")
                run_config["mesh_shape"] = (int(c), int(dd))
            except ValueError:
                parser.error("--mesh-shape must look like '4x2' "
                             f"(got {args.mesh_shape!r})")
        if args.preagg is not None:
            run_config["preagg"] = args.preagg
        if args.bucket_size is not None:
            run_config["bucket_size"] = args.bucket_size
        if (args.topology is not None or args.mixing is not None
                or args.graph_seed is not None):
            topo = dict(run_config.get("topology_config") or {})
            if args.topology is not None:
                topo["graph"] = args.topology
            if args.mixing is not None:
                topo["mixing"] = args.mixing
            if args.graph_seed is not None:
                topo["graph_seed"] = args.graph_seed
            run_config["topology_config"] = topo
        if args.arrivals_json is not None:
            run_config["async_config"] = json.loads(args.arrivals_json)
        if args.state_store is not None:
            run_config["state_store"] = args.state_store
        if args.window is not None:
            run_config["state_window"] = args.window
        if args.data_store is not None:
            run_config["data_store"] = args.data_store
        if args.data_dir is not None:
            run_config["data_dir"] = args.data_dir
        experiments = {
            f"{args.algo.lower()}_run": {
                "run": args.algo,
                "stop": {"training_iteration": args.rounds},
                "config": run_config,
            }
        }

        def _run():
            return run_experiments(
                experiments,
                storage_path=args.storage_path,
                verbose=args.verbose,
                metrics_csv=args.metrics_csv,
                cost_analysis=not args.no_cost_analysis,
                autotune=args.autotune,
                plan_cache_dir=args.plan_cache_dir,
                trace_dir=args.trace_dir,
                watchdog=watchdog,
                flightrec_rounds=args.flightrec_rounds,
            )

    # --trace wraps EITHER subcommand (the run subcommand used to silently
    # ignore it — a one-off run is exactly when you want a profile).
    # --trace-dir composes: armed span annotations land inside this
    # profiler capture.
    if args.trace:
        from blades_tpu.obs.trace import trace

        with trace(args.trace):
            summaries = _run()
    else:
        summaries = _run()
    best = max(summaries, key=lambda s: s["best_test_acc"], default=None)
    if best:
        print(f"best trial: {best['trial']} test_acc={best['best_test_acc']:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
