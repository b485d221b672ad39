"""End-to-end mining driver — the paper's job, CLI form.

  PYTHONPATH=src python -m repro.launch.mine --transactions 20000 --items 256 \
      --min-support 0.02 --max-k 5
  # multi-device (the paper's multi-node mode):
  PYTHONPATH=src python -m repro.launch.mine --host-devices 8 --mesh 4x2 ...
  # mine AND emit a servable rulebook artifact (serving/rulebook.py):
  PYTHONPATH=src python -m repro.launch.mine ... --rulebook rb.npz \
      --min-confidence 0.6 --rule-score confidence --max-rules 8192
  # out-of-core: ingest to an on-disk store, then stream-mine it
  # (host RAM bounded by --stream-chunk-rows, DESIGN.md §9):
  PYTHONPATH=src python -m repro.launch.mine --transactions 2000000 \
      --store /data/quest_2m --ingest --stream-chunk-rows 8192
  # fault-tolerant: checkpoint every 64 chunks; after a crash, rerun with
  # --resume for a dict-identical result (DESIGN.md §11):
  PYTHONPATH=src python -m repro.launch.mine ... --store /data/quest_2m \
      --checkpoint-every 64 [--resume]
  # retryable SON phase 1 over the store's shards:
  PYTHONPATH=src python -m repro.launch.mine ... --store /data/quest_2m \
      --algo son --max-partition-retries 2
  # incremental (DESIGN.md §15): seed the count cache once, then each later
  # run folds ONLY the rows appended since it (dict-identical result):
  PYTHONPATH=src python -m repro.launch.mine ... --store /data/quest_2m \
      --count-cache
  PYTHONPATH=src python -m repro.launch.mine ... --store /data/quest_2m --delta
  # observability (DESIGN.md §13): live per-level progress + Hadoop-style
  # job counters + a perfetto-loadable trace of every mining phase:
  PYTHONPATH=src python -m repro.launch.mine ... --store /data/quest_2m \
      --progress --trace-out mine-trace.json --metrics-out mine-metrics.json

``--rulebook PATH`` compiles the mined itemsets into the packed-bitset rule
columns the Pallas rule-match serving engine consumes (DESIGN.md §8) and
saves them as one ``.npz``; serve it with ``examples/serve_rules.py``.

``--store PATH`` switches the driver to the out-of-core path: the synthetic
DB is ingested CHUNKED into a packed-shard store at PATH (``--ingest``
forces re-ingest; otherwise an existing store is reused) and mined with the
streaming Map/Reduce driver — the dense matrix is never materialized.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--transactions", type=int, default=20_000)
    ap.add_argument("--items", type=int, default=256)
    ap.add_argument("--avg-len", type=float, default=10.0)
    ap.add_argument("--min-support", type=float, default=0.02)
    ap.add_argument("--max-k", type=int, default=6)
    ap.add_argument("--impl", default="auto", choices=["auto", "jnp", "pallas", "pallas_interpret"])
    ap.add_argument("--representation", default="dense", choices=["dense", "packed"],
                    help="device transaction store: dense int8 or packed uint32 bitsets")
    ap.add_argument("--algo", default="levelwise", choices=["levelwise", "son", "naive_paper"])
    ap.add_argument("--partitions", type=int, default=8, help="SON phase-1 partitions")
    ap.add_argument("--host-devices", type=int, default=0)
    ap.add_argument("--mesh", default="", help="e.g. 4x2 = data x model")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rules", action="store_true", help="extract association rules")
    ap.add_argument("--min-confidence", type=float, default=0.6)
    ap.add_argument("--rulebook", default="", metavar="PATH",
                    help="compile + save a servable rulebook artifact (.npz)")
    ap.add_argument("--rule-score", default="confidence", choices=["confidence", "lift"],
                    help="rulebook serving score column")
    ap.add_argument("--max-rules", type=int, default=None,
                    help="truncate the rulebook to the top-scoring rules")
    ap.add_argument("--checkpoint-every", type=int, default=0, metavar="CHUNKS",
                    help="streamed mining: persist a resumable checkpoint next to "
                         "the store manifest every N chunks (0 = level "
                         "boundaries only when --resume is possible, i.e. off)")
    ap.add_argument("--resume", action="store_true",
                    help="resume the streamed mine from the newest committed "
                         "checkpoint in the store's checkpoint dir")
    ap.add_argument("--max-partition-retries", type=int, default=None, metavar="N",
                    help="SON streamed phase 1: run shard mappers through the "
                         "retrying executor with N re-executions per partition")
    ap.add_argument("--count-cache", action="store_true",
                    help="SON streamed mine that ALSO persists the pre-prune "
                         "phase-2 union counts into the store manifest as the "
                         "incremental count cache (DESIGN.md §15, the seed "
                         "for --delta); needs --store")
    ap.add_argument("--delta", action="store_true",
                    help="incremental mine: fold rows appended since the "
                         "count cache generation into it and re-verify only "
                         "novel candidates (core.incremental.mine_delta; "
                         "full-scan fallback on a cold/invalid cache or an "
                         "oversized delta — the report says which); needs "
                         "--store")
    ap.add_argument("--store", default="", metavar="DIR",
                    help="on-disk transaction store: mine out-of-core via the "
                         "streaming driver (ingested here if absent)")
    ap.add_argument("--ingest", action="store_true",
                    help="force (re-)ingest of the synthetic DB into --store")
    ap.add_argument("--stream-chunk-rows", type=int, default=8192,
                    help="rows per streamed chunk (bounds host RAM during mining)")
    ap.add_argument("--shard-rows", type=int, default=8192,
                    help="rows per on-disk shard at ingest (= SON partition size)")
    ap.add_argument("--progress", action="store_true",
                    help="streamed mining: live per-level progress lines with "
                         "rows/s throughput and ETA (stderr)")
    ap.add_argument("--trace-out", default="", metavar="PATH",
                    help="streamed mining: write a Chrome trace-event JSON of "
                         "the mining phase spans (load in ui.perfetto.dev)")
    ap.add_argument("--metrics-out", default="", metavar="PATH",
                    help="streamed mining: write the Hadoop-style job counters "
                         "as JSON")
    args = ap.parse_args()

    if args.host_devices and "XLA_FLAGS" not in os.environ:
        os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={args.host_devices}"
        os.execv(sys.executable, [sys.executable] + sys.argv)

    import numpy as np

    from repro.core.apriori import AprioriConfig, mine
    from repro.core.rules import extract_rules
    from repro.core.son import mine_son
    from repro.data.synthetic import QuestConfig, gen_transactions
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    mesh = None
    data_axes, model_axis = ("data",), None
    if args.mesh:
        from repro.launch.mesh import make_auto_mesh

        dd, mm = (int(x) for x in args.mesh.split("x"))
        mesh = make_auto_mesh((dd, mm), ("data", "model"))
        model_axis = "model"

    qcfg = QuestConfig(
        num_transactions=args.transactions, num_items=args.items,
        avg_len=args.avg_len, seed=args.seed)

    db = store = None
    if args.store:
        from repro.data.store import ingest_quest, open_store

        if args.ingest or not os.path.exists(os.path.join(args.store, "manifest.json")):
            print(f"[mine] ingesting {args.transactions} x {args.items} (chunked) "
                  f"-> {args.store} ...")
            store = ingest_quest(qcfg, args.store, shard_rows=args.shard_rows,
                                 chunk_rows=args.stream_chunk_rows)
        else:
            store = open_store(args.store)
        print(f"[mine] store: n={store.num_transactions} items={store.num_items} "
              f"shards={store.num_partitions}")
    else:
        print(f"[mine] generating {args.transactions} transactions x {args.items} items ...")
        db = gen_transactions(qcfg)

    cfg = AprioriConfig(
        min_support=args.min_support, max_k=args.max_k, count_impl=args.impl,
        representation=args.representation,
        data_axes=data_axes, model_axis=model_axis,
        use_naive_paper_map=(args.algo == "naive_paper"),
    )

    if (args.checkpoint_every or args.resume) and store is None:
        ap.error("--checkpoint-every/--resume need the streamed driver: add --store DIR")
    if (args.count_cache or args.delta) and store is None:
        ap.error("--count-cache/--delta need the on-disk store: add --store DIR")
    if args.max_partition_retries is not None and (
        store is None or (args.algo != "son" and not (args.count_cache or args.delta))
    ):
        ap.error("--max-partition-retries needs --store DIR and --algo son "
                 "(or --count-cache/--delta, which run SON phase 1 inside)")
    if (args.progress or args.trace_out or args.metrics_out) and store is None:
        ap.error("--progress/--trace-out/--metrics-out instrument the streamed "
                 "driver: add --store DIR")

    obs = tracer = None
    if args.progress or args.trace_out or args.metrics_out:
        from repro.obs import MetricsRegistry, MiningObs, MiningProgress, Tracer

        tracer = Tracer(sample_rate=1.0) if args.trace_out else None
        progress = (MiningProgress(total_rows=store.num_transactions)
                    if args.progress else None)
        obs = MiningObs(registry=MetricsRegistry(), tracer=tracer,
                        progress=progress)

    t0 = time.time()
    if store is not None:
        from repro.core.streaming import mine_son_streamed, mine_streamed

        fault = None
        if args.max_partition_retries is not None:
            from repro.distributed.fault_tolerance import FaultConfig

            fault = FaultConfig(max_retries=args.max_partition_retries)
        if args.delta:
            import dataclasses as _dc

            from repro.core import incremental as inc

            res, rep = inc.mine_delta(
                store, cfg, mesh=mesh, chunk_rows=args.stream_chunk_rows,
                fault=fault, checkpoint=True, resume=args.resume, obs=obs)
            print(f"[mine] delta report: {json.dumps(_dc.asdict(rep))}")
        elif args.count_cache:
            from repro.core import incremental as inc

            res, cache = inc.build_count_cache(
                store, cfg, mesh=mesh, chunk_rows=args.stream_chunk_rows,
                fault=fault, obs=obs)
            print(f"[mine] count cache seq={cache.seq} covering n={cache.n} "
                  f"({cache.candidate_total()} cached candidates over levels "
                  f"{sorted(cache.levels)}) -> {store.path}")
        elif args.algo == "son":
            res = mine_son_streamed(store, cfg, mesh=mesh,
                                    chunk_rows=args.stream_chunk_rows, fault=fault,
                                    obs=obs)
            if res.fault_report is not None:
                print(f"[mine] SON fault report: {json.dumps(res.fault_report.to_json())}")
        else:
            use_ckpt = bool(args.checkpoint_every) or args.resume
            if args.resume:
                print(f"[mine] resuming from {store.checkpoint_path} (if a committed "
                      "checkpoint exists)")
            res = mine_streamed(store, cfg, mesh=mesh,
                                chunk_rows=args.stream_chunk_rows,
                                checkpoint=True if use_ckpt else None,
                                checkpoint_every_chunks=args.checkpoint_every,
                                resume=args.resume, obs=obs)
    elif args.algo == "son":
        res = mine_son(db, cfg, mesh=mesh, num_partitions=args.partitions)
    else:
        res = mine(db, cfg, mesh=mesh)
    dt = time.time() - t0

    print(f"[mine] {dt:.2f}s; min_count={res.min_count}")
    for k in sorted(res.levels):
        sets, sup = res.levels[k]
        print(f"  level {k}: {sets.shape[0]:6d} frequent itemsets "
              f"(max support {int(sup.max()) if sup.size else 0})")
    print(f"  total: {res.total_frequent}")

    if args.rules:
        rules = extract_rules(res, min_confidence=args.min_confidence, max_rules=20)
        print(f"[rules] top {len(rules)} by confidence:")
        for r in rules:
            print(f"  {r.antecedent} -> {r.consequent}  conf={r.confidence:.3f} "
                  f"supp={r.support:.4f} lift={r.lift:.2f}")
    if args.rulebook:
        from repro.serving.rulebook import compile_rulebook

        rb = compile_rulebook(
            res, min_confidence=args.min_confidence, score=args.rule_score,
            max_rules=args.max_rules, num_items=args.items,
        )
        rb.save(args.rulebook)
        print(f"[rulebook] {rb.num_rules} rules ({rb.num_rows} padded rows, "
              f"score={rb.score_kind}) -> {args.rulebook}")

    if obs is not None:
        obs.finish()
        if args.trace_out:
            tracer.save_chrome(args.trace_out)
            print(f"[obs] wrote {len(tracer.spans())} spans -> {args.trace_out} "
                  "(load in ui.perfetto.dev)", file=sys.stderr)
        if args.metrics_out:
            counters = obs.counters()
            out = {"seconds": dt, "counters": counters}
            with open(args.metrics_out, "w") as f:
                json.dump(out, f, indent=2)
            print(f"[obs] wrote job counters -> {args.metrics_out}", file=sys.stderr)

    print(json.dumps({"seconds": dt, "total_frequent": res.total_frequent,
                      "levels": {k: int(v[0].shape[0]) for k, v in res.levels.items()}}))


if __name__ == "__main__":
    main()
