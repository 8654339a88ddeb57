"""Command line: ``python -m paddle_tpu
{train,bench,lint,serve,route,accounting,tune,info,convert}``.

reference: the ``paddle`` binary (paddle/trainer/TrainerMain.cpp:32 —
``paddle train``, ``paddle pserver``, ``paddle merge_model``; launch wrapper
paddle/scripts/submit_local.sh.in:173). TPU redesign: there is no pserver
role — distribution is SPMD sharding — so the surviving verbs are train
(drive a user config), bench (the benchmark harnesses), convert (dataset ->
recordio shards), info (device/platform report).
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys


def _load_config(path):
    spec = importlib.util.spec_from_file_location("train_config", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cmd_train(args):
    """Config contract: the file defines ``model()`` returning a dict with
    keys cost, feed_list, reader (and optionally optimizer, num_passes)."""
    import paddle_tpu as pt

    cfg = _load_config(args.config)
    spec = cfg.model()
    optimizer = spec.get("optimizer") or pt.optimizer.SGD(
        learning_rate=args.learning_rate)
    trainer = pt.Trainer(cost=spec["cost"], optimizer=optimizer,
                         feed_list=spec["feed_list"],
                         checkpoint_dir=args.checkpoint_dir or None)

    def handler(e):
        if isinstance(e, pt.trainer_mod.EndIteration):
            if e.batch_id % args.log_period == 0:
                print("pass %d batch %d cost %.5f"
                      % (e.pass_id, e.batch_id, e.cost))
        elif isinstance(e, pt.trainer_mod.EndPass):
            print("pass %d done: %s" % (e.pass_id, e.metrics))

    trainer.train(spec["reader"],
                  num_passes=args.num_passes or spec.get("num_passes", 1),
                  event_handler=handler)
    return 0


def cmd_bench(args):
    sys.argv = [sys.argv[0]] + (args.extra or [])
    if args.suite == "resnet":
        import bench
        # `paddle_tpu bench resnet [batch [steps]]` == `python bench.py ...`
        return bench.main(args.extra or [])
    elif args.suite == "image":
        from benchmark import image_bench
        print(json.dumps(image_bench.bench(model=args.model or "resnet50",
                                           batch_size=args.batch_size)))
    elif args.suite == "rnn":
        from benchmark import rnn_bench
        print(json.dumps(rnn_bench.bench(batch_size=args.batch_size)))
    return 0


def _parse_mesh(spec, verb):
    """'dp=4,tp=2' -> {axis: size}; malformed entries — missing '=',
    non-integer or < 1 sizes, empty segments from a stray comma — are
    REJECTED with a readable message (returns None): silently skipping
    one would price/verify a different mesh than the operator asked
    for."""
    spec = (spec or "").strip()
    if not spec:
        return {}
    mesh = {}
    for pair in spec.split(","):
        k, eq, v = pair.partition("=")
        try:
            if not (eq and k.strip()):
                raise ValueError("missing '='")
            size = int(v)
            if size < 1:
                raise ValueError("size < 1")
            mesh[k.strip()] = size
        except ValueError:
            print("%s: bad --mesh entry %r (want axis=size with "
                  "size >= 1, e.g. 'dp=8' or 'dp=4,tp=2')" % (verb, pair))
            return None
    return mesh


def _append_train_step(verb, spec, main, startup):
    """Append backward + optimizer ops to ``main`` so the memory pass
    prices the TRAIN step, not just the forward build. A config with a
    cost but no optimizer gets the same default SGD ``cmd_train``
    would use — ``paddle_tpu train`` of that config runs a full
    backward, so pricing it forward-only would report a peak far below
    what the train run allocates. Returns True on success; a minimize
    failure is reported (one consistent line across the lint and
    accounting surfaces) and degrades to forward-only analysis."""
    import paddle_tpu as pt
    if not (isinstance(spec, dict) and spec.get("cost") is not None):
        return False
    optimizer = spec.get("optimizer") or pt.optimizer.SGD(
        learning_rate=0.01)
    try:
        with pt.program_guard(main, startup):
            optimizer.minimize(spec["cost"])
    except Exception as e:
        print("%s: could not append the backward (%s: %s); analysing "
              "the forward program only" % (verb, type(e).__name__, e))
        return False
    return True


def _parse_specs(pairs, verb):
    """``--spec var=dim0,dim1,...`` entries -> {var: spec tuple}. Each
    dim token is a mesh axis name, several joined with '+', or empty /
    '-' for a replicated dim (``--spec "x=dp,tp"``,
    ``--spec "w=fsdp+tp,-"``). Malformed entries are REJECTED with a
    readable message (returns None) — silently skipping one would
    verify different shardings than the operator seeded."""
    out = {}
    for pair in pairs or []:
        name, eq, spec = pair.partition("=")
        if not (eq and name.strip()):
            print("%s: bad --spec entry %r (want var=axis,axis,... with "
                  "empty or '-' for a replicated dim and '+' joining "
                  "multi-axis dims, e.g. 'x=dp,tp' or 'w=fsdp+tp,-')"
                  % (verb, pair))
            return None
        entries = []
        for tok in spec.split(","):
            tok = tok.strip()
            if not tok or tok == "-":
                entries.append(None)
            elif "+" in tok:
                entries.append(tuple(a.strip() for a in tok.split("+")
                                     if a.strip()))
            else:
                entries.append(tok)
        out[name.strip()] = tuple(entries)
    return out


def cmd_lint(args):
    """Statically verify the program a train config builds — same config
    contract as ``train`` (the file defines ``model()``) but nothing is
    executed or compiled: the Program IR is built and handed to
    paddle_tpu.analysis.verify. ``--comm`` adds the
    collective-consistency pass (PT020-PT023) over the parameter set's
    grads template at ``--comm-axis`` replicas under the comm_* flags
    (or ``--comm-policy``/``--comm-hosts`` overrides). ``--memory``
    adds the static memory planner (PT030-PT033): the backward +
    optimizer ops are appended (when the config names an optimizer) so
    the liveness pass sees the full training step, and the predicted
    per-device peak is checked against ``--budget-gb`` /
    ``FLAGS.memory_budget_gb`` at ``--batch`` over ``--mesh dp=N``.
    ``--sharding`` adds the static sharding analyzer (PT040-PT045):
    PartitionSpec propagation over ``--mesh`` (e.g.
    ``--mesh dp=4,fsdp=2,tp=2``), with ``--spec var=dp,tp`` overriding
    or seeding individual entries; when it runs, the memory pass prices
    sharded (not replicated) persistable state from the propagated
    specs. ``--all`` runs every pass with one combined summary.
    Exit 0 clean / warnings-only, 1 on error diagnostics (or any
    diagnostic with --strict), 2 if the config itself fails to build."""
    import paddle_tpu as pt
    from paddle_tpu import analysis

    if args.all:
        args.comm = args.memory = args.sharding = True
    main, startup = pt.Program(), pt.Program()
    try:
        cfg = _load_config(args.config)
        with pt.program_guard(main, startup):
            spec = cfg.model()
    except Exception as e:
        print("lint: config %r failed to build: %s: %s"
              % (args.config, type(e).__name__, e))
        return 2
    fetches = None
    if isinstance(spec, dict) and spec.get("cost") is not None:
        # metrics (accuracy etc.) count as fetch roots too: a trainer
        # fetches them per pass, so they are not dead ops
        fetches = [spec["cost"]] + list(spec.get("metrics", ()))
    diags = analysis.verify(main, fetches=fetches)
    startup_diags = analysis.verify(startup)
    comm_diags = []
    memory_diags = []
    sharding_diags = []
    sharding_plan = None
    reports = [("main program", diags), ("startup program", startup_diags)]
    train_step = None
    if args.sharding:
        from paddle_tpu.analysis import sharding as sharding_mod
        mesh = _parse_mesh(args.mesh, "lint")
        if mesh is None:
            return 2
        overrides = _parse_specs(getattr(args, "spec", None), "lint")
        if overrides is None:
            return 2
        if overrides:
            merged = dict(getattr(main, "_shardings", None) or {})
            merged.update(overrides)
            main._shardings = merged
        # the spec question is about the TRAIN step too: grads must
        # co-shard with their params (PT044) and the optimizer updates
        # are where that contract is checked
        train_step = _append_train_step("lint", spec, main, startup)
        sharding_plan, sharding_diags = sharding_mod.check_sharding(
            main, mesh_shape=mesh)
        print("sharding pass (%s program):"
              % ("train-step" if train_step else "forward-only"))
        print(sharding_plan.table())
        reports.append(("sharding pass", sharding_diags))
    if args.memory:
        from paddle_tpu.analysis import memory as memory_mod
        mesh = _parse_mesh(args.mesh, "lint")
        if mesh is None:
            return 2
        shard_specs = sharding_plan.specs if sharding_plan is not None \
            else (getattr(main, "_shardings", None) or None)
        ignored = sorted(a for a in mesh if a != "dp")
        if ignored and not shard_specs:
            # the batch shards over dp only — with no spec table the
            # params price replicated; saying so beats silently pricing
            # a different mesh than asked (run --sharding to fix)
            print("lint: --memory shards the batch over 'dp' only; "
                  "mesh axis(es) %s ignored (params priced replicated)"
                  % ", ".join(ignored))
        # the residency question is about the TRAIN step: append
        # backward + optimizer ops so activations-to-backward and
        # gradient lifetimes are in the walk (the structural rules
        # above already ran on the as-built program)
        if train_step is None:
            train_step = _append_train_step("lint", spec, main, startup)
        budget = memory_mod.resolve_budget_bytes(
            budget_gb=args.budget_gb or None)
        plan, memory_diags = memory_mod.check_memory(
            main, budget_bytes=budget, batch=args.batch,
            fetches=fetches, dp=mesh.get("dp", 1),
            specs=shard_specs, mesh_shape=mesh if shard_specs else None)
        print("memory pass (%s program):"
              % ("train-step" if train_step else "forward-only"))
        print(plan.table(budget))
        reports.append(("memory pass", memory_diags))
    if args.comm:
        from paddle_tpu.analysis import comm_rules
        from paddle_tpu import comm as comm_mod
        tpl = comm_rules.grads_template_from_program(main)
        if not tpl:
            # no row in the report either: a "clean" verdict for checks
            # that never executed would misreport the gate log
            print("comm pass: no static-shaped parameters; skipped")
        else:
            try:
                policy = comm_mod.resolve_policy(
                    base=args.comm_policy or None,
                    hosts=args.comm_hosts or None,
                    axis_size=args.comm_axis)
                comm_diags, fp = comm_rules.verify_comm(
                    tpl, policy, axis_size=args.comm_axis)
            except ValueError as e:
                print("lint: bad comm options: %s" % e)
                return 2
            print("comm pass: %d grad leaves, axis=%d, %r -> "
                  "fingerprint %s" % (len(tpl), args.comm_axis, policy,
                                      fp))
            reports.append(("comm pass", comm_diags))
    for label, ds in reports:
        report = analysis.render_diagnostics(ds, label=label)
        print(report if report else "%s: clean" % label)
    if args.dot:
        from paddle_tpu import debugger
        # errors always fill red; the PT015+ dataflow/comm families
        # highlight at any severity — their findings are exactly the
        # ops a reader wants to see on the graph
        bad_ops = {d.op_idx for d in diags + sharding_diags
                   if d.block_idx == 0 and d.op_idx is not None
                   and (d.is_error or d.code >= "PT015")}
        debugger.draw_block_graphviz(main.global_block(),
                                     op_highlights=bad_ops, path=args.dot)
        print("lint: wrote %s (%d op(s) highlighted)"
              % (args.dot, len(bad_ops)))
    all_diags = diags + startup_diags + comm_diags + memory_diags \
        + sharding_diags
    failed = any(d.is_error for d in all_diags) \
        or (args.strict and all_diags)
    if args.all:
        errs = sum(1 for d in all_diags if d.is_error)
        warns = len(all_diags) - errs
        print("lint --all: %d pass(es), %d error(s), %d warning(s) -> %s"
              % (len(reports), errs, warns,
                 "FAIL" if failed else "clean"))
    return 1 if failed else 0


def _parse_extra_models(pairs, primary=None):
    """``--extra_model name=dir`` entries -> [(name, dir)]; raises
    ValueError on a malformed pair or a name collision (two extras, or
    an extra shadowing ``primary``/``--name`` — load_model would
    silently hot-swap the earlier artifact)."""
    out = []
    seen = {primary} if primary else set()
    for pair in pairs or []:
        name, eq, dirname = pair.partition("=")
        if not (eq and name.strip() and dirname.strip()):
            raise ValueError("bad --extra_model %r (want name=dir)" % pair)
        name = name.strip()
        if name in seen:
            raise ValueError("duplicate model name %r (--extra_model "
                             "must not repeat a name or shadow --name)"
                             % name)
        seen.add(name)
        out.append((name, dirname.strip()))
    return out


def _validate_artifacts(verb, artifact_dir, extra_models, kv_pages=None,
                        page_tokens=None, draft_dir=None):
    """Validate the primary + every extra artifact up front; prints the
    problems and returns False on a bad one (nothing gets started).
    ``kv_pages``/``page_tokens``: the CLI's pool overrides — PT034 must
    size the pool the engine will ACTUALLY allocate, not the flag
    default. Beyond the per-model check, the AGGREGATE of every
    co-hosted generative model (weights + pool each) is checked
    against the budget: one process loads them all, so each fitting
    alone proves nothing. ``draft_dir`` (a ``--draft_dir`` speculation
    draft) joins the aggregate the same way — it costs its weights plus
    its own page pool; a speculative ARTIFACT needs no extra entry,
    its draft side is already priced into its own bytes."""
    from paddle_tpu import inference
    from paddle_tpu.analysis import memory as memory_mod
    budget = memory_mod.resolve_budget_bytes()
    if draft_dir and not inference.is_generative_artifact(draft_dir):
        print("%s: cannot serve: --draft_dir %r is not a generative "
              "artifact (speculation drafts are export_generative "
              "directories)" % (verb, draft_dir), file=sys.stderr)
        return False
    total, gen_labels = 0, []
    entries = [("artifact", artifact_dir)] + [
        ("extra model %r" % n, d) for n, d in extra_models]
    if draft_dir:
        entries.append(("speculation draft", draft_dir))
    for label, dirname in entries:
        generative = inference.is_generative_artifact(dirname)
        problems = (inference.validate_generative_artifact(
                        dirname, kv_pages=kv_pages,
                        page_tokens=page_tokens)
                    if generative else inference.validate_artifact(dirname))
        if problems:
            print("%s: cannot serve %s %r:" % (verb, label, dirname),
                  file=sys.stderr)
            for p in problems:
                print("  - " + p, file=sys.stderr)
            return False
        if generative and budget:
            nb = inference.generative_memory_bytes(
                dirname, kv_pages=kv_pages, page_tokens=page_tokens)
            if nb is not None:
                total += nb
                gen_labels.append("%s=%s" % (label,
                                             memory_mod.fmt_bytes(nb)))
    if budget and len(gen_labels) > 1 and total > budget:
        print("%s: cannot serve: PT034 the co-hosted generative models "
              "need %s together (%s) on a %s budget — each fits alone, "
              "one process loads them all"
              % (verb, memory_mod.fmt_bytes(total),
                 ", ".join(gen_labels), memory_mod.fmt_bytes(budget)),
              file=sys.stderr)
        return False
    return True


def cmd_serve(args):
    """Serve a compiled OR generative artifact over HTTP
    (paddle_tpu.serving): validate the artifact directory (exit 1,
    readable message, nothing started on a bad one), register + warm it
    — a generative artifact stands a continuous-batching engine up
    behind ``:generate`` — then run the JSON endpoint until
    SIGTERM/SIGINT, which drains cleanly and exits 0. Repeatable
    ``--extra_model name=dir`` entries publish additional artifacts
    from the same process (how a router replica serves a predict model
    and a generate model side by side)."""
    from paddle_tpu import inference, serving
    from paddle_tpu.flags import FLAGS

    try:
        extra_models = _parse_extra_models(args.extra_model,
                                           primary=args.name)
    except ValueError as e:
        print("serve: %s" % e, file=sys.stderr)
        return 1
    generative = inference.is_generative_artifact(args.artifact_dir)
    draft_dir = args.draft_dir or FLAGS.serve_draft_dir or None
    if draft_dir and not generative:
        print("serve: --draft_dir only pairs with a generative primary "
              "artifact", file=sys.stderr)
        return 1
    if not _validate_artifacts("serve", args.artifact_dir, extra_models,
                               kv_pages=args.kv_pages or None,
                               page_tokens=args.page_tokens or None,
                               draft_dir=draft_dir):
        return 1
    service = serving.InferenceService(
        max_batch=args.max_batch or None,
        batch_timeout_ms=(args.batch_timeout_ms
                          if args.batch_timeout_ms >= 0 else None),
        queue_depth=args.queue_depth or None,
        tier=args.tier or None)
    gen_overrides = {}
    if args.max_running:
        gen_overrides["max_running"] = args.max_running
    if args.kv_pages:
        gen_overrides["kv_pages"] = args.kv_pages
    if args.page_tokens:
        gen_overrides["page_tokens"] = args.page_tokens
    if args.prefix_sharing:
        gen_overrides["prefix_sharing"] = True
    # speculation plumbing for the PRIMARY model only: an external
    # --draft_dir loads here; a speculative artifact needs nothing —
    # the registry auto-detects and pairs it on load
    primary_overrides = dict(gen_overrides)
    if args.spec_k:
        primary_overrides["spec_k"] = args.spec_k
    loading = args.artifact_dir
    try:
        if draft_dir:
            loading = draft_dir
            primary_overrides["draft_model"] = \
                inference.load_generative(draft_dir)
            primary_overrides.setdefault("spec_k",
                                         FLAGS.serve_spec_k)
        loading = args.artifact_dir
        entry = service.load_model(
            args.name, args.artifact_dir,
            **(primary_overrides if generative else {}))
        for extra_name, extra_dir in extra_models:
            loading = extra_dir
            service.load_model(
                extra_name, extra_dir,
                **(gen_overrides
                   if inference.is_generative_artifact(extra_dir) else {}))
    except Exception as e:
        print("serve: failed to load %r: %s: %s"
              % (loading, type(e).__name__, e), file=sys.stderr)
        service.close()
        return 1
    server = serving.make_server(service, host=args.host, port=args.port)
    host, port = server.server_address[:2]
    # one parseable readiness line: smoke tests and operators read the
    # bound port from here (--port 0 binds a free one)
    import jax
    dev = jax.devices()[0]
    info = {
        "host": host, "port": port, "model": args.name,
        "platform": dev.platform, "device_kind": dev.device_kind,
        "kind": "generative" if generative else "compiled",
        "version": entry.version, "warmup_ms": round(entry.warmup_ms, 3),
        "max_batch": service.max_batch,
        "batch_timeout_ms": service.batch_timeout_ms}
    if service.tier:
        info["tier"] = service.tier
    if extra_models:
        info["extra_models"] = [n for n, _ in extra_models]
    if generative:
        info.update({"max_running": entry.engine.max_running,
                     "kv_pages": entry.engine.pool.num_pages,
                     "page_tokens": entry.engine.pool.page_tokens,
                     "max_context": entry.engine.max_context})
        st = entry.engine.stats
        if st["speculative"] or st["spec_degraded"]:
            info.update({"speculative": st["speculative"],
                         "spec_k": st["spec_k"],
                         "spec_degraded": st["spec_degraded"]})
        if st.get("prefix_sharing") or st.get("prefix_degraded"):
            info.update({"prefix_sharing": st["prefix_sharing"],
                         "prefix_degraded": st["prefix_degraded"]})
    print(json.dumps({"serving": info}), flush=True)
    try:
        signum = serving.httpd.serve_until_shutdown(server)
    finally:
        # snapshot BEFORE close(): close drops the generation engines,
        # and the shutdown record is the run's serving evidence
        final_stats = service.stats
        server.server_close()
        service.close()
    # the degraded-mode audit trail rides the shutdown record: a build
    # that fell back (device_sample_degraded, prefix_degraded, ...) says
    # so on the one line operators and the chip smoke already parse
    from paddle_tpu import resilience
    print(json.dumps({"serving_stopped": {
        "signal": signum, "stats": final_stats,
        "events": [{"kind": e["kind"], "site": e.get("site")}
                   for e in resilience.events()]}}), flush=True)
    return 0


def cmd_route(args):
    """Front a fleet of ``serve`` replicas with the multi-replica router
    (paddle_tpu.serving.router): validate the artifact(s), spawn and
    supervise ``--replicas`` worker processes (SIGTERM->SIGKILL drain,
    RetryPolicy restarts on crash), and run the proxy tier —
    least-loaded routing from polled /statz, health eject/probation,
    one failover retry, rolling ``:reload`` — until SIGTERM/SIGINT,
    which drains the fleet and exits 0. With ``--autoscale`` the
    closed-loop controller (paddle_tpu.serving.autoscale) grows and
    shrinks the fleet on the smoothed pressure signal within
    [--min_replicas, --max_replicas]."""
    from paddle_tpu.flags import FLAGS
    from paddle_tpu.serving import (Autoscaler, ReplicaPool, Router,
                                    httpd, make_router_server)

    if args.state_dir:
        # the audit trail: every record_durable_event() in this process
        # (router ejections/failovers, autoscale decisions, breaker
        # transitions, gray verdicts) defaults its events.jsonl here,
        # so the evidence survives a router crash
        os.makedirs(args.state_dir, exist_ok=True)
        os.environ["PADDLE_TPU_ELASTIC_STATE"] = args.state_dir
    try:
        extra_models = _parse_extra_models(args.extra_model,
                                           primary=args.name)
    except ValueError as e:
        print("route: %s" % e, file=sys.stderr)
        return 1
    if not _validate_artifacts("route", args.artifact_dir, extra_models,
                               kv_pages=args.kv_pages or None,
                               page_tokens=args.page_tokens or None,
                               draft_dir=args.draft_dir or None):
        return 1
    serve_args = []
    if args.draft_dir:
        serve_args += ["--draft_dir", args.draft_dir]
    if args.spec_k:
        serve_args += ["--spec_k", str(args.spec_k)]
    if args.max_batch:
        serve_args += ["--max_batch", str(args.max_batch)]
    if args.batch_timeout_ms >= 0:
        serve_args += ["--batch_timeout_ms", str(args.batch_timeout_ms)]
    if args.queue_depth:
        serve_args += ["--queue_depth", str(args.queue_depth)]
    if args.max_running:
        serve_args += ["--max_running", str(args.max_running)]
    if args.kv_pages:
        serve_args += ["--kv_pages", str(args.kv_pages)]
    if args.page_tokens:
        serve_args += ["--page_tokens", str(args.page_tokens)]
    if args.prefix_sharing:
        serve_args += ["--prefix_sharing"]
    for n, d in extra_models:
        serve_args += ["--extra_model", "%s=%s" % (n, d)]
    tier_counts = None
    serve_args_overrides = {}
    tier_of = {}
    if args.tiers:
        tier_counts = {}
        try:
            for part in args.tiers.split(","):
                k, _, v = part.partition("=")
                k = k.strip()
                if k not in ("prefill", "decode"):
                    raise ValueError("unknown tier %r" % k)
                tier_counts[k] = int(v)
                if tier_counts[k] < 1:
                    raise ValueError("tier %r wants >= 1 replica" % k)
        except ValueError as e:
            print("route: bad --tiers %r: %s" % (args.tiers, e),
                  file=sys.stderr)
            return 1
        if set(tier_counts) != {"prefill", "decode"}:
            print("route: --tiers wants BOTH classes, e.g. "
                  "prefill=1,decode=2", file=sys.stderr)
            return 1
        initial = sum(tier_counts.values())
        if args.replicas and args.replicas != initial:
            print("route: --tiers fixes the fleet size at %d; drop "
                  "--replicas" % initial, file=sys.stderr)
            return 1
        idx = 0
        for t in ("prefill", "decode"):
            for _ in range(tier_counts[t]):
                serve_args_overrides[idx] = ["--tier", t]
                tier_of[idx] = t
                idx += 1
        # per-tier autoscale budget: each class may grow by `headroom`
        # above its configured floor (default: double the tier)
        tier_headroom = (max(args.max_replicas - initial, 0)
                         if args.max_replicas else initial)
    elif args.autoscale:
        max_replicas = args.max_replicas or max(args.min_replicas,
                                                FLAGS.route_replicas)
        if args.min_replicas < 1 or max_replicas < args.min_replicas:
            print("route: --autoscale wants 1 <= min_replicas <= "
                  "max_replicas, got [%d, %d]"
                  % (args.min_replicas, max_replicas), file=sys.stderr)
            return 1
        initial = args.replicas or args.min_replicas
        if not args.min_replicas <= initial <= max_replicas:
            # a fleet starting outside the budget is one the controller
            # can never bring inside it (it shrinks one replica per
            # quiet window, and only when the load is quiet)
            print("route: --autoscale wants --replicas inside "
                  "[%d, %d], got %d"
                  % (args.min_replicas, max_replicas, initial),
                  file=sys.stderr)
            return 1
    else:
        initial = args.replicas or FLAGS.route_replicas
    try:
        pool = ReplicaPool(
            args.artifact_dir, initial,
            name=args.name, host=args.host, serve_args=serve_args,
            serve_args_overrides=serve_args_overrides or None,
            restart_budget=(args.restart_budget if args.restart_budget >= 0
                            else None),
            grace_sec=args.grace_sec)
        pool.start(wait=True)
    except Exception as e:
        print("route: %s" % e, file=sys.stderr)
        return 1
    router = None
    autoscalers = []
    try:
        # anything failing before the serve loop (say, the router port
        # already bound) must still drain the fleet pool.start spawned
        # — no orphan serve workers on an exception
        router = Router(pool, policy=args.policy,
                        poll_ms=args.poll_ms if args.poll_ms > 0 else None,
                        state_dir=args.state_dir or None)
        router.poll_once()
        router.start_polling()
        if args.autoscale and tier_counts:
            # one controller PER serving class, each on its
            # class-correct signal (queue depth / page occupancy)
            autoscalers = [
                Autoscaler(
                    router, pool, tier=t,
                    min_replicas=tier_counts[t],
                    max_replicas=tier_counts[t] + tier_headroom,
                    cooldown_s=(args.cooldown_s
                                if args.cooldown_s >= 0 else None))
                for t in ("prefill", "decode")]
            router.autoscaler = list(autoscalers)
            for a in autoscalers:
                a.start()
        elif args.autoscale:
            autoscalers = [Autoscaler(
                router, pool, min_replicas=args.min_replicas,
                max_replicas=max_replicas,
                up_pressure=(args.scale_up_pressure
                             if args.scale_up_pressure > 0 else None),
                down_pressure=(args.scale_down_pressure
                               if args.scale_down_pressure >= 0
                               else None),
                cooldown_s=(args.cooldown_s
                            if args.cooldown_s >= 0 else None))]
            router.autoscaler = autoscalers[0]
            autoscalers[0].start()
        server = make_router_server(router, host=args.host,
                                    port=args.port)
    except Exception as e:
        for a in autoscalers:
            a.close()
        if router is not None:
            router.close()
        pool.stop()
        print("route: %s: %s" % (type(e).__name__, e), file=sys.stderr)
        return 1
    host, port = server.server_address[:2]
    info = {
        "host": host, "port": port, "model": args.name,
        "policy": router.policy,
        "replicas": [dict({"index": w["index"], "port": w["port"],
                           "pid": w["pid"]},
                          **({"tier": tier_of[w["index"]]}
                             if w["index"] in tier_of else {}))
                     for w in pool.describe()["workers"]]}
    if tier_counts:
        info["tiers"] = dict(tier_counts)
    if len(autoscalers) == 1 and autoscalers[0].tier is None:
        a = autoscalers[0]
        info["autoscale"] = {
            "min_replicas": a.min_replicas,
            "max_replicas": a.max_replicas,
            "up_pressure": a.up_pressure,
            "down_pressure": a.down_pressure,
            "cooldown_s": a.cooldown_s}
    elif autoscalers:
        info["autoscale"] = [
            {"tier": a.tier, "min_replicas": a.min_replicas,
             "max_replicas": a.max_replicas,
             "up_pressure": a.up_pressure,
             "down_pressure": a.down_pressure,
             "cooldown_s": a.cooldown_s} for a in autoscalers]
    print(json.dumps({"router": info}), flush=True)
    try:
        signum = httpd.serve_until_shutdown(server)
    finally:
        final_stats = None
        try:
            # stats/close can take a couple of seconds (the close joins
            # the poller) — a second Ctrl-C landing there must still
            # drain the fleet, so pool.stop() is not gated on them
            for a in autoscalers:
                a.close()
            final_stats = router.stats()
            server.server_close()
            router.close()
        finally:
            pool.stop()
    print(json.dumps({"router_stopped": {
        "signal": signum, "stats": final_stats}}), flush=True)
    return 0


def cmd_accounting(args):
    """Quantify a train config's gradient-communication design: the
    per-chip collective byte counts of the transpiled parameter set
    (parallel.accounting ring formulas) plus the paddle_tpu.comm policy
    matrix — bytes-on-wire and dispatch counts for
    none/fused/hierarchical/int8 over the requested mesh — plus the
    ``memory`` columns: per-device params / optimizer state /
    activations / gradients / feeds and the predicted peak from the
    static memory planner (analysis.memory) at ``--batch``, the
    per-parameter-class sizing table the FSDP direction needs as
    input. ``--sharding`` adds the propagated-PartitionSpec plan
    (analysis.sharding): per-class spec table, fingerprint, priced
    implicit reshards, and any PT040-PT045 diagnostics as a
    ``sharding`` section. ``--generative DIR`` adds a ``kv_pool``
    section: the artifact's physical-page KV residency with
    dedup-ratio capacity columns (``--dedup-ratio``; speculative
    pairings fold the draft in). Pure analysis: nothing is compiled or
    executed, no devices needed. Same config contract as
    ``train``/``lint`` (the file defines ``model()``)."""
    import paddle_tpu as pt
    from paddle_tpu.parallel import accounting

    mesh_shape = _parse_mesh(args.mesh or "dp=8", "accounting")
    if mesh_shape is None:
        return 2
    main, startup = pt.Program(), pt.Program()
    try:
        cfg = _load_config(args.config)
        with pt.program_guard(main, startup):
            spec = cfg.model()
    except Exception as e:
        print("accounting: config %r failed to build: %s: %s"
              % (args.config, type(e).__name__, e))
        return 2
    # memory columns price the TRAIN step (optimizer slots, grads,
    # activations-to-backward); comm tables read parameters only,
    # which minimize() does not change
    train_step = _append_train_step("accounting", spec, main, startup)
    fetches = [spec["cost"]] if train_step else None
    specs = getattr(main, "_shardings", None) or {}
    try:
        report = {
            "mesh": mesh_shape,
            "collectives": accounting.collective_bytes(
                main, specs, mesh_shape),
            "comm": accounting.comm_policy_table(
                main, specs, mesh_shape, hosts=args.hosts or None,
                bucket_mb=args.bucket_mb or None,
                split_ratio=(args.split_ratio
                             if args.split_ratio >= 0 else None)),
            "memory": dict(
                accounting.memory_table(main, mesh_shape,
                                        batch=args.batch,
                                        fetches=fetches),
                train_step=train_step),
        }
        if args.generative:
            from paddle_tpu import inference as _inf
            res = _inf.generative_residency(
                args.generative, dedup_ratio=args.dedup_ratio)
            if res is None:
                print("accounting: --generative %r is not a readable "
                      "generative artifact" % args.generative)
                return 2
            report["kv_pool"] = res
        if args.sharding:
            from paddle_tpu.analysis import sharding as sharding_mod
            plan, sharding_diags = sharding_mod.check_sharding(
                main, mesh_shape=mesh_shape)
            report["sharding"] = dict(
                plan.summary(),
                diagnostics=[{"code": d.code,
                              "severity": d.severity,
                              "message": d.message,
                              "location": d.location()}
                             for d in sharding_diags])
    except ValueError as e:
        # e.g. --hosts not dividing the data axis: readable, not a trace
        print("accounting: %s" % e)
        return 2
    print(json.dumps(report, indent=2))
    return 0


def _tune_populations(program, batch, compute_dtype=None):
    """Walk the program and collect the tunable-kernel shape keys its ops
    actually hit: conv2d ops inside the conv3x3 kernel's population,
    flash_attention ops, and mul gemms inside the matmul kernel's. The
    feed batch dim (-1) substitutes ``batch``. Returns
    [(kernel, key_dict)], deduplicated, declaration order.

    ``compute_dtype`` overrides the IR-declared var dtype for the conv
    and mul keys: dispatch keys on the dtype the op RUNS at, and under
    AMP that is bfloat16 (amp.cast_inputs fires before tune.lookup), not
    the declared float32 — winners tuned at the wrong dtype would never
    hit. Defaults to bfloat16 when the program is AMP-marked."""
    from paddle_tpu.kernels.conv3x3 import supports_conv3x3
    from paddle_tpu.kernels.matmul import supports_matmul

    if compute_dtype is None and getattr(program, "_amp", False):
        compute_dtype = "bfloat16"

    def shape_of(block, name):
        v = block._find_var_recursive(name)
        if v is None or v.shape is None:
            return None
        return tuple(batch if int(s) == -1 else int(s) for s in v.shape)

    def run_dtype(block, name):
        if compute_dtype:
            return compute_dtype
        v = block._find_var_recursive(name)
        return str(getattr(v, "dtype", "float32") or "float32")

    out, seen = [], set()

    def add(kernel, key):
        k = (kernel, tuple(sorted(key.items())))
        if k not in seen:
            seen.add(k)
            out.append((kernel, key))

    for block in program.blocks:
        for op in block.ops:
            if op.type == "conv2d":
                xs = shape_of(block, op.input("Input")[0])
                ws = shape_of(block, op.input("Filter")[0])
                if not xs or not ws or len(xs) != 4:
                    continue
                s = op.attr("strides", [1, 1])
                p = op.attr("paddings", [0, 0])
                d = op.attr("dilations", [1, 1])
                g = op.attr("groups", 1) or 1
                if supports_conv3x3(ws, s, p, d, g):
                    n, c, h, w = xs
                    dt = run_dtype(block, op.input("Input")[0])
                    add("conv3x3", {"n": n, "h": h, "w": w, "c": c,
                                    "o": int(ws[0]), "dtype": dt})
            elif op.type == "flash_attention":
                qs = shape_of(block, op.input("Q")[0])
                if not qs or len(qs) != 4:
                    continue
                # no AMP override: attention_ops does not amp-cast, so
                # the op runs at the declared q dtype
                qv = block._find_var_recursive(op.input("Q")[0])
                dt = str(getattr(qv, "dtype", "float32") or "float32")
                vs = shape_of(block, op.input("V")[0])
                key = {"b": qs[0], "s": qs[1], "h": qs[2], "d": qs[3],
                       "causal": bool(op.attr("causal", False)),
                       "dtype": dt}
                if vs and len(vs) == 4 and vs[3] != qs[3]:
                    key["dv"] = vs[3]   # as ops/attention_ops.attention
                add("flash_attention", key)
            elif op.type == "mul":
                xs = shape_of(block, op.input("X")[0])
                ys = shape_of(block, op.input("Y")[0])
                if not xs or not ys:
                    continue
                xn = op.attr("x_num_col_dims", 1)
                yn = op.attr("y_num_col_dims", 1)
                m = 1
                for v in xs[:xn]:
                    m *= v
                k = 1
                for v in xs[xn:]:
                    k *= v
                n = 1
                for v in ys[yn:]:
                    n *= v
                dt = run_dtype(block, op.input("X")[0])
                if supports_matmul((m, k), (k, n), dt):
                    add("matmul", {"m": m, "k": k, "n": n, "dtype": dt})
    return out


def _gen_artifact_populations(dirname):
    """The paged-attention population a generative artifact's SERVING
    deployment would dispatch on: one key per pool geometry, built from
    the artifact's transformer config plus the serve flags
    (``serve_max_running`` / ``serve_page_tokens``) — the exact
    ``population_key`` the engine consults at construction, so a winner
    tuned here is the winner the engine re-hits. Raises ValueError when
    the artifact's config JSON is unreadable."""
    from paddle_tpu.flags import FLAGS
    from paddle_tpu.inference import GEN_CONFIG_FILE
    from paddle_tpu.kernels.paged_attention import population_key
    from paddle_tpu.serving.kvcache import pages_for
    try:
        with open(os.path.join(dirname, GEN_CONFIG_FILE)) as f:
            cfg = json.load(f)["config"]
        hidden, heads = int(cfg["hidden"]), int(cfg["num_heads"])
        max_seq = int(cfg["max_seq"])
    except Exception as e:
        raise ValueError("generative artifact %r: %s unreadable (%s: %s)"
                         % (dirname, GEN_CONFIG_FILE,
                            type(e).__name__, e)) from e
    page_tokens = int(FLAGS.serve_page_tokens)
    key = population_key(FLAGS.serve_max_running,
                         pages_for(max_seq, page_tokens),
                         page_tokens, heads, hidden // max(heads, 1))
    return [("paged_attention", key)]


def cmd_tune(args):
    """Autotune the Pallas kernels a train config's program actually
    uses (paddle_tpu.tune): enumerate each kernel's valid configs for
    the shapes in the program, compile+parity-check+time every
    candidate, persist winners in the per-(device, shape) cache, and
    print the winners table. ``--dry-run`` only enumerates. Exit 0 on
    success, 1 when a population ends with zero eligible candidates,
    2 when the config fails to build.

    ``config`` may also be a generative-artifact DIRECTORY (an
    ``export_generative`` output): the population is then the
    paged-attention decode key for the deployment geometry the serve
    flags describe, and the cached winner is exactly what
    ``GenerationEngine`` consults when it compiles its decode step."""
    import paddle_tpu as pt
    from paddle_tpu import tune as tune_mod
    from paddle_tpu.tune import results as results_mod
    from paddle_tpu import inference as _inf

    if os.path.isdir(args.config) and _inf.is_generative_artifact(
            args.config):
        try:
            pops = _gen_artifact_populations(args.config)
        except ValueError as e:
            print("tune: %s" % e, file=sys.stderr)
            return 2
    else:
        main, startup = pt.Program(), pt.Program()
        try:
            cfg_mod = _load_config(args.config)
            with pt.program_guard(main, startup):
                cfg_mod.model()
        except Exception as e:
            print("tune: config %r failed to build: %s: %s"
                  % (args.config, type(e).__name__, e), file=sys.stderr)
            return 2
        pops = _tune_populations(main, args.batch,
                                 compute_dtype=args.dtype or None)
    if not pops:
        print("tune: no tunable kernel populations in %r (conv3x3 / "
              "flash_attention / matmul shapes)" % args.config)
        return 0
    from paddle_tpu.flags import FLAGS
    dev = results_mod.device_kind()
    budget = args.budget if args.budget > 0 else (FLAGS.tune_budget or
                                                  None)
    timer = None
    if args.timer == "wall":
        timer = tune_mod.wall_timer()
    elif args.timer == "model":
        timer = tune_mod.model_timer()
    if args.dry_run:
        # same budget arithmetic as the real loop (stock rung included),
        # so the printed count is exactly what a run would time
        print("%-16s %-44s %10s" % ("kernel", "signature", "candidates"))
        for kernel, key in pops:
            space = tune_mod.get_space(kernel)
            cands = space.candidates(
                key, budget=(budget - 1) if budget else None)
            print("%-16s %-44s %10d"
                  % (kernel, tune_mod.signature(key), len(cands) + 1))
        print("tune: dry run — nothing timed, nothing cached")
        return 0
    from paddle_tpu import profiler as _prof
    rows, failed = [], 0
    cache = tune_mod.WinnerCache()
    print("%-16s %-44s %-34s %12s %6s" % ("kernel", "signature", "winner",
                                          "time", "cands"))
    for kernel, key in pops:
        res = tune_mod.autotune(kernel, key, timer=timer, budget=budget,
                                cache=cache)
        _prof.update_tune_counters(tune_loops=1,
                                   tune_candidates=len(res.records))
        rows.append(res.row())
        if not res.ok:
            failed += 1
            print("%-16s %-44s %-34s %12s %6d"
                  % (kernel, res.sig, "<NO ELIGIBLE CANDIDATE>", "-",
                     len(res.records)))
            continue
        win = ("xla" if res.winner.get("use") == "xla" else
               ",".join("%s=%s" % kv for kv in sorted(res.winner.items())))
        print("%-16s %-44s %-34s %10.3fms %6d"
              % (kernel, res.sig, win, res.winner_seconds * 1e3,
                 len(res.records)))
    rec = results_mod.bench_record(
        "tune", rows, device=dev,
        meta={"config": args.config, "batch": args.batch,
              "budget": budget or 0,
              "timer": rows and rows[0]["timer"] or None,
              "cache_dir": cache.cache_dir})
    path = results_mod.write_result(rec, path=args.out)
    print("tune: %d population(s), %d failed; winners cached in %s; "
          "evidence %s" % (len(pops), failed, cache.path, path))
    return 1 if failed else 0


def cmd_info(args):
    import jax

    import paddle_tpu as pt
    devs = jax.devices()
    print(json.dumps({
        "version": pt.__version__,
        "platform": devs[0].platform,
        "device_count": len(devs),
        "devices": [str(d) for d in devs],
        "registered_ops": len(pt.ops.registered_ops()),
        "native_runtime": pt.native.available(),
    }, indent=2))
    return 0


def cmd_convert(args):
    import paddle_tpu as pt

    mod = pt.dataset
    for part in args.dataset.split("."):
        mod = getattr(mod, part)
    reader = getattr(mod, args.split)()
    paths = pt.dataset.common.convert(args.output, reader,
                                      args.records_per_shard, args.dataset)
    print(json.dumps({"shards": paths}))
    return 0


def main(argv=None):
    p = argparse.ArgumentParser(prog="paddle_tpu")
    sub = p.add_subparsers(dest="cmd", required=True)

    t = sub.add_parser("train", help="train a model config")
    t.add_argument("config")
    t.add_argument("--num_passes", type=int, default=0)
    t.add_argument("--learning_rate", type=float, default=0.01)
    t.add_argument("--checkpoint_dir", default="")
    t.add_argument("--log_period", type=int, default=10)
    t.set_defaults(fn=cmd_train)

    b = sub.add_parser("bench", help="run a benchmark suite")
    b.add_argument("suite", choices=["resnet", "image", "rnn"])
    b.add_argument("--model", default=None)
    b.add_argument("--batch_size", type=int, default=64)
    b.add_argument("extra", nargs="*")
    b.set_defaults(fn=cmd_bench)

    lint = sub.add_parser(
        "lint", help="statically verify a train config's Program IR "
                     "(paddle_tpu.analysis; exit 1 on PT errors)")
    lint.add_argument("config")
    lint.add_argument("--dot", default=None, metavar="PATH",
                      help="write a graphviz .dot of the main block with "
                           "failing ops highlighted")
    lint.add_argument("--strict", action="store_true",
                      help="treat warnings as failures")
    lint.add_argument("--comm", action="store_true",
                      help="run the collective-consistency pass "
                           "(PT020-PT023) over the config's parameter "
                           "grads template: bucket plan coverage, "
                           "canonical issue order, (host, chip) "
                           "axis-group factorisation, overlap schedule "
                           "vs gradient finalisation")
    lint.add_argument("--comm-axis", type=int, default=8,
                      dest="comm_axis",
                      help="data-axis size (replica count) the comm "
                           "pass checks against")
    lint.add_argument("--comm-policy", default="", dest="comm_policy",
                      help="comm policy base for the pass (empty = "
                           "FLAGS.comm_policy)")
    lint.add_argument("--comm-hosts", type=int, default=0,
                      dest="comm_hosts",
                      help="host count for the hierarchical/multipath "
                           "factorisation (0 = FLAGS.comm_hosts)")
    lint.add_argument("--memory", action="store_true",
                      help="run the static memory planner (PT030-PT033, "
                           "analysis.memory): liveness-based per-device "
                           "peak-HBM prediction over the full train step "
                           "(backward + optimizer appended when the "
                           "config names one), checked against the "
                           "budget; prints the residency table")
    lint.add_argument("--budget-gb", type=float, default=0.0,
                      dest="budget_gb",
                      help="per-device HBM budget for --memory (GiB; "
                           "0 = FLAGS.memory_budget_gb, which at 0 "
                           "leaves PT030 unchecked — the honest default "
                           "on a devbox with no TPU attached)")
    lint.add_argument("--batch", type=int, default=16,
                      help="global batch substituted for the feed "
                           "wildcard dim (-1) in the --memory pass")
    lint.add_argument("--mesh", default="dp=1",
                      help="mesh for the --memory/--sharding passes, "
                           "e.g. 'dp=8' or 'dp=4,fsdp=2,tp=2': the "
                           "batch shards over dp; params replicate "
                           "unless --sharding propagates their specs")
    lint.add_argument("--sharding", action="store_true",
                      help="run the static sharding analyzer "
                           "(PT040-PT045, analysis.sharding): propagate "
                           "PartitionSpecs through the train step over "
                           "--mesh, price implicit reshards, and audit "
                           "the sharded collective vocabulary; prints "
                           "the sharding plan table")
    lint.add_argument("--spec", action="append", default=None,
                      metavar="VAR=SPEC",
                      help="override/seed one variable's PartitionSpec "
                           "for --sharding (repeatable), e.g. "
                           "--spec 'x=dp,tp' --spec 'w=fsdp+tp,-' "
                           "(',' separates dims, '+' joins axes on one "
                           "dim, '-' or empty = replicated dim)")
    lint.add_argument("--all", action="store_true",
                      help="run every pass (structural + --comm + "
                           "--memory + --sharding) with one combined "
                           "summary and exit code")
    lint.set_defaults(fn=cmd_lint)

    sv = sub.add_parser(
        "serve", help="serve a compiled or generative artifact over "
                      "HTTP (paddle_tpu.serving; generative artifacts "
                      "get continuous-batching :generate; SIGTERM "
                      "drains and exits 0)")
    sv.add_argument("artifact_dir",
                    help="directory written by inference.export_compiled "
                         "or inference.export_generative")
    sv.add_argument("--name", default="default",
                    help="model name in the registry / URL")
    sv.add_argument("--host", default="127.0.0.1")
    sv.add_argument("--port", type=int, default=8500,
                    help="0 binds a free port (printed on the readiness "
                         "line)")
    sv.add_argument("--max_batch", type=int, default=0,
                    help="override FLAGS.serve_max_batch (0 = flag)")
    sv.add_argument("--batch_timeout_ms", type=float, default=-1.0,
                    help="override FLAGS.serve_batch_timeout_ms "
                         "(negative = flag)")
    sv.add_argument("--queue_depth", type=int, default=0,
                    help="override FLAGS.serve_queue_depth (0 = flag)")
    sv.add_argument("--max_running", type=int, default=0,
                    help="generative artifacts: override "
                         "FLAGS.serve_max_running (0 = flag)")
    sv.add_argument("--kv_pages", type=int, default=0,
                    help="generative artifacts: override "
                         "FLAGS.serve_kv_pages (0 = flag)")
    sv.add_argument("--page_tokens", type=int, default=0,
                    help="generative artifacts: override "
                         "FLAGS.serve_page_tokens (0 = flag)")
    sv.add_argument("--draft_dir", default="",
                    help="generative artifacts: pair a draft model "
                         "(an export_generative directory, same "
                         "vocabulary) for speculative decoding; empty "
                         "defers to FLAGS.serve_draft_dir / a paired "
                         "speculative artifact's own draft")
    sv.add_argument("--spec_k", type=int, default=0,
                    help="generative artifacts: speculation depth "
                         "override (0 = FLAGS.serve_spec_k or the "
                         "paired artifact's qualified k)")
    sv.add_argument("--prefix_sharing", "--prefix-sharing",
                    action="store_true",
                    help="generative artifacts: copy-on-write prefix "
                         "sharing over the paged KV pool — concurrent "
                         "same-prefix requests pin one physical copy "
                         "of their shared prefill pages (greedy output "
                         "stays bit-identical; default "
                         "FLAGS.serve_prefix_sharing)")
    sv.add_argument("--tier", default="", choices=["", "prefill",
                                                   "decode"],
                    help="serving class for a disaggregated fleet "
                         "(advertised through /statz so the router "
                         "two-hops :generate as prefill -> handoff -> "
                         "decode); empty = a do-everything replica")
    sv.add_argument("--extra_model", action="append", default=[],
                    metavar="NAME=DIR",
                    help="additional artifact(s) to publish from the "
                         "same process (repeatable): a router replica "
                         "serves its predict and generate models side "
                         "by side this way")
    sv.set_defaults(fn=cmd_serve)

    rt = sub.add_parser(
        "route", help="front N supervised `serve` replicas with the "
                      "multi-replica router (paddle_tpu.serving.router: "
                      "least-loaded proxying, health eject/probation, "
                      "failover, rolling :reload; SIGTERM drains the "
                      "fleet and exits 0)")
    rt.add_argument("artifact_dir",
                    help="artifact every replica serves (compiled or "
                         "generative; see also --extra_model)")
    rt.add_argument("--name", default="default",
                    help="model name in the registry / URL")
    rt.add_argument("--host", default="127.0.0.1")
    rt.add_argument("--port", type=int, default=8600,
                    help="router port; 0 binds a free one (printed on "
                         "the readiness line). Replicas always bind "
                         "free ports")
    rt.add_argument("--replicas", type=int, default=0,
                    help="worker process count (0 = "
                         "FLAGS.route_replicas)")
    rt.add_argument("--policy", choices=["least_loaded", "round_robin"],
                    default="least_loaded",
                    help="replica selection: least_loaded scores each "
                         "replica from its polled /statz (queue depth + "
                         "generation backlog + KV pressure) plus live "
                         "in-flight counts; round_robin is the "
                         "load-blind baseline benchmark/load_bench.py "
                         "compares against")
    rt.add_argument("--poll_ms", type=int, default=0,
                    help="health/load poll interval (0 = "
                         "FLAGS.route_poll_ms)")
    rt.add_argument("--restart_budget", type=int, default=-1,
                    help="restarts per dead replica before declaring it "
                         "lost (negative = FLAGS.route_restart_budget)")
    rt.add_argument("--autoscale", action="store_true",
                    help="close the loop on the pressure signal "
                         "(paddle_tpu.serving.autoscale): grow/shrink "
                         "the fleet between --min_replicas and "
                         "--max_replicas from the EWMA-smoothed "
                         "per-model pressure in /statz — scale-up "
                         "after a sustained overload, drain-first "
                         "scale-down after a longer quiet window, "
                         "crash-loop circuit breaker on dying "
                         "scale-ups")
    rt.add_argument("--min_replicas", "--min-replicas", type=int,
                    default=1,
                    help="autoscale floor (also the initial fleet size "
                         "when --autoscale is on and --replicas is 0)")
    rt.add_argument("--max_replicas", "--max-replicas", type=int,
                    default=0,
                    help="autoscale ceiling (0 = max(min_replicas, "
                         "FLAGS.route_replicas))")
    rt.add_argument("--scale_up_pressure", "--scale-up-pressure",
                    type=float, default=0.0,
                    help="smoothed pressure that triggers a scale-up "
                         "after k_up consecutive control ticks (0 = "
                         "FLAGS.route_scale_up_pressure)")
    rt.add_argument("--scale_down_pressure", "--scale-down-pressure",
                    type=float, default=-1.0,
                    help="smoothed pressure under which the (longer) "
                         "quiet window triggers a drain-first "
                         "scale-down (negative = "
                         "FLAGS.route_scale_down_pressure)")
    rt.add_argument("--cooldown_s", "--cooldown-s", type=float,
                    default=-1.0,
                    help="minimum seconds between scale-ups; the "
                         "scale-down cooldown is 2x (negative = "
                         "FLAGS.route_cooldown_s)")
    rt.add_argument("--grace_sec", type=float, default=5.0,
                    help="SIGTERM drain window before the pool "
                         "escalates to SIGKILL at shutdown")
    rt.add_argument("--max_batch", type=int, default=0,
                    help="forwarded to every replica (0 = flag)")
    rt.add_argument("--batch_timeout_ms", type=float, default=-1.0,
                    help="forwarded to every replica (negative = flag)")
    rt.add_argument("--queue_depth", type=int, default=0,
                    help="forwarded to every replica (0 = flag)")
    rt.add_argument("--max_running", type=int, default=0,
                    help="forwarded to every replica (0 = flag)")
    rt.add_argument("--kv_pages", type=int, default=0,
                    help="forwarded to every replica (0 = flag)")
    rt.add_argument("--page_tokens", type=int, default=0,
                    help="forwarded to every replica (0 = flag)")
    rt.add_argument("--draft_dir", default="",
                    help="speculation draft forwarded to every replica "
                         "(empty = none)")
    rt.add_argument("--spec_k", type=int, default=0,
                    help="speculation depth forwarded to every replica "
                         "(0 = flag/artifact default)")
    rt.add_argument("--prefix_sharing", "--prefix-sharing",
                    action="store_true",
                    help="forward copy-on-write KV prefix sharing to "
                         "every replica")
    rt.add_argument("--tiers", default="",
                    help="disaggregated fleet layout, e.g. "
                         "'prefill=1,decode=2': the first N replicas "
                         "serve --tier prefill, the rest --tier decode, "
                         "and the router two-hops :generate as "
                         "prefill -> handoff -> decode (fault site "
                         "serving.ship: a failed hop re-prefills on "
                         "the decode tier). With --autoscale each tier "
                         "gets its OWN controller on its class-correct "
                         "signal (queue depth / page occupancy), "
                         "floored at its configured count")
    rt.add_argument("--extra_model", action="append", default=[],
                    metavar="NAME=DIR",
                    help="additional artifact(s) every replica publishes "
                         "(repeatable)")
    rt.add_argument("--state-dir", "--state_dir", default=None,
                    dest="state_dir",
                    help="durable event directory (events.jsonl): "
                         "ejections, failovers, breaker transitions, "
                         "autoscale decisions and gray-failure verdicts "
                         "survive a router crash — the serving twin of "
                         "launch --state-dir")
    rt.set_defaults(fn=cmd_route)

    acc = sub.add_parser(
        "accounting", help="per-chip collective bytes + comm-policy "
                           "matrix for a train config (paddle_tpu.comm; "
                           "pure analysis, no devices)")
    acc.add_argument("config")
    acc.add_argument("--mesh", default="dp=8",
                     help="mesh axis sizes, e.g. 'dp=8' or 'dp=4,tp=2'")
    acc.add_argument("--hosts", type=int, default=0,
                     help="host count for the hierarchical rows "
                          "(0 = 2 when the axis divides, else flat)")
    acc.add_argument("--bucket_mb", type=float, default=0.0,
                     help="override FLAGS.comm_bucket_mb (0 = flag)")
    acc.add_argument("--batch", type=int, default=16,
                     help="global batch for the memory columns (shards "
                          "over the data axis; feeds' wildcard dim)")
    acc.add_argument("--split-ratio", type=float, default=-1.0,
                     dest="split_ratio",
                     help="primary-path fraction for the multipath rows "
                          "(negative = FLAGS.comm_split_ratio; derive "
                          "from measured bandwidths via "
                          "comm.measured_split_ratio)")
    acc.add_argument("--generative", default="", metavar="DIR",
                     help="also price a generative artifact's KV-pool "
                          "residency (inference.generative_residency): "
                          "physical pages/bytes + the dedup-ratio "
                          "capacity columns as a 'kv_pool' section; a "
                          "speculative pairing folds the draft in")
    acc.add_argument("--dedup-ratio", type=float, default=1.0,
                     dest="dedup_ratio",
                     help="prefix-sharing dedup ratio to price the "
                          "--generative capacity columns at (1.0 = no "
                          "sharing; e.g. the live pool's observed "
                          "dedup_ratio stat)")
    acc.add_argument("--sharding", action="store_true",
                     help="add the propagated-PartitionSpec plan "
                          "(analysis.sharding PT040-PT045): per-class "
                          "spec table, fingerprint, priced implicit "
                          "reshards, diagnostics")
    acc.set_defaults(fn=cmd_accounting)

    tn = sub.add_parser(
        "tune", help="autotune the Pallas kernels a train config uses "
                     "(paddle_tpu.tune; winners persist per device+shape)")
    tn.add_argument("config",
                    help="train config .py, or a generative-artifact "
                         "directory (export_generative output) — the "
                         "latter tunes the paged-attention decode key "
                         "for the serve-flag pool geometry")
    tn.add_argument("--batch", type=int, default=8,
                    help="batch size substituted for the feed dim (-1) "
                         "when deriving kernel shapes")
    tn.add_argument("--dtype", default=None,
                    help="compute dtype for the conv/matmul keys (e.g. "
                         "bfloat16). Default: bfloat16 when the config "
                         "builds an AMP-marked program — dispatch keys "
                         "on the dtype the op RUNS at — else the "
                         "declared var dtype")
    tn.add_argument("--budget", type=int, default=0,
                    help="cap candidates per (kernel, shape), stock-XLA "
                         "rung included (0 = FLAGS.tune_budget)")
    tn.add_argument("--dry-run", action="store_true",
                    help="enumerate populations and candidate counts "
                         "only; nothing timed or cached")
    tn.add_argument("--timer", choices=["auto", "wall", "model"],
                    default="auto",
                    help="auto = wall clock on the tpu, deterministic "
                         "model timer elsewhere (CPU interpret-mode wall "
                         "times are noise)")
    tn.add_argument("--out", default=None, metavar="PATH",
                    help="evidence-record path (default "
                         "benchmark/results/tune_<device>.json)")
    tn.set_defaults(fn=cmd_tune)

    i = sub.add_parser("info", help="device / build report")
    i.set_defaults(fn=cmd_info)

    c = sub.add_parser("convert", help="dataset -> recordio shards")
    c.add_argument("dataset")
    c.add_argument("--split", default="train")
    c.add_argument("--output", default="./recordio")
    c.add_argument("--records_per_shard", type=int, default=4096)
    c.set_defaults(fn=cmd_convert)

    # the reference exposed cluster fan-out through the same binary
    # (`paddle train/pserver`, scripts/cluster_train); mirror that shape
    from .launch import add_launch_arguments
    ln = sub.add_parser(
        "launch", help="multi-process launcher — fail-fast or "
                       "--elastic survive-and-resize (see "
                       "paddle_tpu.launch / paddle_tpu.elastic)")
    add_launch_arguments(ln)
    ln.add_argument("script_argv", nargs=argparse.REMAINDER)

    def cmd_launch(args):
        from .launch import _shell_rc, run_from_args
        if not args.script_argv:
            p.error("launch: missing training script")
        return _shell_rc(run_from_args(args, args.script_argv))

    ln.set_defaults(fn=cmd_launch)

    args = p.parse_args(argv)
    return args.fn(args)
