"""Checked registry of every fault-injection site.

Pure data, import-free (tools/lint parses this file with stdlib
``ast``; the chaos suite imports it).  One source of truth for three
previously hand-kept lists:

* the site table in `docs/resilience.md` is GENERATED from this dict
  (``python -m tools.lint --gen-docs`` rewrites the block between the
  ``lint:sites`` markers);
* `tools/chaos_suite.py` derives its schedule draw (`chaos_sites`) and
  corruption targets (`chaos_corrupt_targets`) from it;
* the static analyzer (rule ``fault-site-registry``) checks that every
  literal site passed to `resilience.faults.maybe_inject` /
  ``corrupt`` in source is registered here, and that
  every registered site appears in the docs table.

Fields per site: ``boundary`` (docs-table cell), ``corruptible``
(honors nan/flip output corruption), ``chaos`` (drawn by the chaos
suite's randomized schedule — multi-process-only and bench-only sites
stay out), ``dynamic`` (the site name reaches the injection call
through a variable, so the analyzer does not require a source
literal).
"""

SITES = {
    "execute_stack": {
        "boundary": "`acc.smm.execute_stack` per driver launch",
        "corruptible": True, "chaos": True, "dynamic": False,
    },
    "execute_superstack": {
        "boundary": "`acc.smm.execute_superstack` per fused C-bin launch "
                    "(`docs/performance.md`)",
        # corruption honored at the fused boundary, but kept out of the
        # randomized chaos draw (historical set): the fused engine's
        # fault recovery is pinned by targeted tests in
        # tests/test_resilience.py instead
        "corruptible": True, "chaos": False, "dynamic": False,
    },
    "prepare_stack": {
        "boundary": "`acc.smm.prepare_stack` (host-side planning)",
        "corruptible": False, "chaos": True, "dynamic": False,
    },
    "dense": {
        "boundary": "the whole-panel dense canvas path in "
                    "`mm.multiply` (one failover, one corruption hook)",
        "corruptible": True, "chaos": True, "dynamic": False,
    },
    "format_plan": {
        "boundary": "the storage-format planner's decision boundary "
                    "(`mm.format_planner.choose`) — a fault degrades "
                    "the plan to the stack format for that product "
                    "only, never cached (labels `name`)",
        "corruptible": False, "chaos": True, "dynamic": False,
    },
    "multihost_init": {
        "boundary": "`parallel.multihost.init_multihost`",
        # multi-process world joins cannot fire inside the single-process
        # chaos suite
        "corruptible": False, "chaos": False, "dynamic": False,
    },
    "collective": {
        "boundary": "`parallel.sparse_dist` mesh dispatch boundary",
        # kept out of the randomized draw (historical set): the mesh
        # corpus cases fault the tick edges below instead
        "corruptible": False, "chaos": False, "dynamic": False,
    },
    "mesh_shift": {
        "boundary": "the double-buffered Cannon tick/shift boundary "
                    "(`parallel.overlap.run_ticks`, one per ring shift; "
                    "labels `engine`, `tick`)",
        "corruptible": True, "chaos": True, "dynamic": True,
    },
    "gather_chunk": {
        "boundary": "the chunked all-gather pipeline's per-shard ring "
                    "step on rectangular grids (same `run_ticks` edge, "
                    "breaker `gather_pipe`; labels `engine`, `tick`)",
        "corruptible": True, "chaos": True, "dynamic": True,
    },
    "tas_tick": {
        "boundary": "the staggered grouped-TAS metronome's tick/shift "
                    "edge (breaker `cannon_db` keyed engine=\"tas\")",
        "corruptible": True, "chaos": True, "dynamic": True,
    },
    "incremental": {
        "boundary": "the delta-aware incremental multiply's splice path "
                    "(`mm.incremental`; raise/oom abort the splice and "
                    "fall back to a full recompute, nan/flip corrupt the "
                    "spliced C — `docs/resilience.md` § incremental)",
        "corruptible": True, "chaos": True, "dynamic": False,
    },
    "attribution": {
        "boundary": "the cost-attribution billing boundary "
                    "(`obs.attribution.bill_window`) — a fault is "
                    "observed (bus event + counter) but ALWAYS "
                    "swallowed before any ledger mutation, so the "
                    "books stay balanced (labels `requests`, "
                    "`request_id`)",
        "corruptible": False, "chaos": True, "dynamic": False,
    },
    "serve_admit": {
        "boundary": "serving-plane admission (`serve.queue`) — a fault "
                    "sheds the submission with a structured rejection "
                    "(labels `tenant`, `request_id`; `docs/serving.md`)",
        "corruptible": False, "chaos": True, "dynamic": False,
    },
    "serve_execute": {
        "boundary": "the serving worker's group-execution boundary "
                    "(`serve.engine`) — a coalesced group degrades to "
                    "serialized, a lone request fails TRANSIENT (labels "
                    "`request_id`, `n`)",
        "corruptible": True, "chaos": True, "dynamic": False,
    },
    "replay_submit": {
        "boundary": "the workload-replay submission choke point "
                    "(`serve.workload.replay_submit`, the load harness "
                    "and the chaos replay case both go through it) — a "
                    "fault sheds the replayed submission before it "
                    "reaches the engine (labels `tenant`, "
                    "`request_id`; `docs/loadtest.md`)",
        "corruptible": False, "chaos": True, "dynamic": False,
    },
    "fleet_route": {
        "boundary": "the fleet router's placement/submit boundary "
                    "(`serve.router.FleetRouter` — a fault fails the "
                    "routed attempt, exercising the retry/backoff and "
                    "re-placement paths; labels `tenant`, `worker`, "
                    "`request_id`; `docs/serving.md` § fleet)",
        # multi-process serving topology: driven deterministically by
        # the fleet_storm corpus case and the fleet tests, never by the
        # single-process randomized draw (the multihost_init precedent)
        "corruptible": False, "chaos": False, "dynamic": False,
    },
    "worker_heartbeat": {
        "boundary": "the fleet router's per-worker heartbeat probe "
                    "(`serve.router.FleetRouter.check` — a fault counts "
                    "as a missed beat, driving the UP -> SUSPECT -> "
                    "DOWN suspicion ladder; labels `worker`)",
        "corruptible": False, "chaos": False, "dynamic": False,
    },
    "fleet_handoff": {
        "boundary": "the exactly-once failover boundary "
                    "(`serve.router.FleetRouter.failover` — a fault "
                    "aborts the handoff attempt before any replay "
                    "lands; the journal survives for the retry; labels "
                    "`worker`, `target`)",
        "corruptible": False, "chaos": False, "dynamic": False,
    },
    "tune_trial": {
        "boundary": "the online autotuner's trial boundary "
                    "(`tune.trials`, one per candidate sweep; labels "
                    "`mnk`, `dtype`) — a fault aborts the trial and NO "
                    "promotion may land from it "
                    "(`docs/autotuning.md` § trial runner)",
        # off the hot path by construction: a faulted trial is absorbed
        # by the tuner (counted, never promoted); in the randomized
        # chaos draw the spec simply never fires outside the dedicated
        # tune_storm corpus case, which also drives it deterministically
        "corruptible": False, "chaos": True, "dynamic": False,
    },
}

# driver labels a fault spec's *target* may also match at a site
# (``pallas:nan`` fires on execute_stack launches whose plan driver is
# pallas) — drawn by the chaos suite alongside the sites themselves
DRIVER_TARGETS = ("xla", "xla_group", "host", "pallas")


def chaos_sites() -> tuple:
    """The chaos suite's schedule-draw targets: every ``chaos`` site
    plus the driver labels."""
    return tuple(
        s for s, meta in SITES.items() if meta["chaos"]) + DRIVER_TARGETS


def chaos_corrupt_targets() -> tuple:
    """Targets whose OUTPUT a nan/flip spec can corrupt in the chaos
    suite: corruptible chaos sites plus the driver labels (a driver
    label fires on the execute_stack corrupt hook)."""
    return tuple(
        s for s, meta in SITES.items()
        if meta["chaos"] and meta["corruptible"]) + DRIVER_TARGETS
