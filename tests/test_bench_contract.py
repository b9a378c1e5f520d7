"""The driver-facing bench contract: `python bench.py` must print exactly
one stdout JSON line with the fields the round driver parses
(metric/value/unit/vs_baseline), the device it ran on
(platform/device_kind/device_count) and each config's self-diagnosis
fields. Runs the REAL entry script in a subprocess, pinned to the CPU from
outside, so a regression in arg parsing or the JSON emission fails here.
Schema, count, parity and zero-lost assertions only: a CPU timing ratio
proves nothing about the chip (ROADMAP D6), so none is gated here.

`bench.py` never re-routes: a platform that cannot initialise is a
non-zero exit with no metric line (test_bench_never_reroutes)."""

import json
import os
import subprocess
import sys
import tempfile

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(argv, timeout=420):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"      # tests are CPU runs, said from outside
    # generated CSVs go to the tmp dir, not the checkout
    env["OTPU_BENCH_DIR"] = os.path.join(tempfile.gettempdir(),
                                         "otpu_bench_contract")
    # serving config: 40 requests keep the unbucketed phase (one XLA
    # compile per distinct size — the pathology under test) under ~15 s
    env["OTPU_SERVE_REQUESTS"] = "40"
    return subprocess.run([sys.executable] + argv, capture_output=True,
                          text=True, timeout=timeout, cwd=REPO, env=env)


@pytest.mark.parametrize("argv,metric,extra_keys", [
    # --epochs 8 (not the shipped 100): the CONTRACT is under test, not
    # the measurement convention, and 92 fewer replay epochs keep this
    # suite member under ~40 s
    (["bench.py", "--rows", "30000", "--epochs", "8"],
     "criteo_hashed_logreg_rows_per_sec_per_chip",
     {"train_rows_x_epochs_per_sec_per_chip", "defer_epoch1", "epoch1_s",
      "replay_source", "cache_overflow", "baseline", "holdout_auc",
      # baseline provenance: the proxy constant + its derivation must ride
      # every record (a bare "proxy-estimate" tag has no audit trail)
      "baseline_value", "baseline_note",
      # optimizer A/B self-description: the RESOLVED rule/lowerings and
      # the dense arm measured in the same run
      "optim_update", "sparse_lowering",
      "pure_step_ms_dense", "optim_step_speedup",
      # cache-codec economics (ISSUE 4): resolved dtype, measured cache
      # bytes, f32-equivalent compression and rows-at-budget capacity,
      # plus the same-run f32-cache step arm
      "cache_dtype", "cache_bytes", "compression_ratio",
      "cache_rows_capacity", "pure_step_ms_f32cache",
      "cache_step_speedup", "encode_s",
      # obs A/B (ISSUE 7): the same-run spans+registry-on vs OTPU_OBS=0
      # step arm, and the embedded registry snapshot
      "obs_overhead_pct", "pure_step_ms_obs", "obs",
      # flake-proofing: each <2% gate earns ONE structured re-measure;
      # both readings ride the record so a banked retry is auditable
      "obs_ab_retried", "prof_ab_retried",
      # goodput & memory attribution (ISSUE 12): the five-way wall
      # decomposition, the device-memory ledger, and the same-run
      # OTPU_PROF on/off step A/B
      "goodput", "ledger", "prof_overhead_pct", "pure_step_ms_prof"}),
    (["bench_suite.py", "--config", "5", "--rows-scale", "0.002"],
     "taxi_kmeans_pca_pipeline",
     {"staged_speedup", "workflow_fit_s"}),
    # first-class taxi pipeline (ROADMAP item 5): the config-5 fit and
    # transform arms promoted into bench.py, plus the streaming-fit arm
    # and the whole-workflow fused-serving A/B (one bucketed AOT dispatch
    # per request vs the OTPU_WORKFLOW_SERVE=0 stage-by-stage path),
    # semantics-gated below on the fused speedup, the dispatch counts,
    # and cross-arm parity
    (["bench.py", "--config", "taxi_pipeline", "--rows", "30000"],
     "taxi_kmeans_pca_pipeline",
     {"workflow_fit_s", "workflow_fit_staged_s", "fit_staged_speedup",
      "refit_fallbacks", "transform_eager_s", "transform_staged_s",
      "staged_speedup", "staged_rows_per_sec_per_chip",
      "streaming_fit_s", "streaming_fit_rows_per_s_per_chip",
      "streaming_scaler_max_abs_diff", "baseline_value", "baseline_note",
      "serve_requests", "request_rows", "workflow_n_stages",
      "serve_fused_p50_ms", "serve_staged_p50_ms",
      "workflow_fused_speedup", "workflow_ab_retried",
      "workflow_fused_speedup_first", "dispatch_fused", "dispatch_staged",
      "workflow_parity"}),
    # serving contract: the bucketed-AOT predict path's JSON line must
    # carry the latency percentiles and the compile-count pair the
    # acceptance criterion is judged on (ISSUE 2), schema-checked here so
    # a field rename fails in CI instead of in the round-end capture
    (["bench.py", "--config", "serving", "--rows", "30000"],
     "criteo_serving_predict_rows_per_sec_per_chip",
     {"p50_ms", "p99_ms", "recompiles", "bucket_hits",
      "recompiles_unbucketed", "compile_reduction", "p50_ms_unbucketed",
      "p99_ms_unbucketed", "pad_overhead", "mb_merge_factor",
      "warmup_buckets", "baseline_value", "baseline_note",
      # trace-context coverage (ISSUE 9): every bucketed-phase request
      # minted a trace id at its serving entry
      "traced_requests", "trace_coverage", "flight_bundles_written"}),
    # resilience fault arm (ISSUE 6): the recovery-overhead A/B line must
    # carry the fields the acceptance criterion is judged on — bounded
    # retries absorbing injected faults bitwise, and the watchdog
    # converting a wedged dispatch into a typed error within budget
    (["bench.py", "--config", "fault"],
     "fault_recovery_streaming_fit_rows_per_sec_per_chip",
     {"recovery_overhead_pct", "wall_clean_s", "wall_fault_s",
      "faults_injected", "retries", "retry_wait_s", "parity_bitwise",
      "watchdog_raised"}),
    # overload-protection A/B (ISSUE 8): the admission-controlled arm
    # keeps p99 bounded vs the legacy unbounded queue and sheds with
    # typed errors — zero hung/lost futures — while OTPU_RESILIENCE=0
    # reproduces legacy behavior; plus the breaker half-open re-admission
    # and the memory-pressure brownout drills
    # serving-fleet A/B (ISSUE 10): the multi-replica layer's measured
    # claims — N-replica aggregate-throughput scaling, hedged-vs-unhedged
    # tail latency under one injected straggler, the SIGKILL-mid-burst
    # accounting (0 lost / 0 hung), the zero-downtime rollout with
    # forced-bad-version rollback, cross-process trace coverage, and the
    # OTPU_FLEET=0 single-process parity pin
    (["bench.py", "--config", "fleet"],
     "fleet_n_replica_scaling",
     {"replicas", "scaling_factor", "scaling_retried",
      "scaling_factor_first",
      "throughput_single_rows_per_s_per_chip",
      "throughput_fleet_rows_per_s_per_chip", "p99_ms_unhedged",
      "p99_ms_hedged", "hedged_p99_ratio", "hedges_issued",
      "kill_requests", "kill_completed", "kill_typed_failures",
      "kill_hung", "kill_lost", "replica_restarted",
      "killed_replica_readmitted", "rollout_outcome",
      "rollout_failed_requests", "rollback_outcome",
      "rollback_current_untouched", "kill_switch_local_parity",
      "baseline_value", "baseline_note",
      "traced_requests", "trace_coverage", "flight_bundles_written",
      # fleet telemetry plane (ISSUE 11): the collector-overhead A/B,
      # the aggregated fleet snapshot + staleness, the SLO burn drill's
      # alert + single rate-limited fleet incident bundle, and the
      # OTPU_FLEETOBS=0 parity pin
      "collector_overhead_pct", "scrape_stale_replicas",
      "fleet_agg_rpc_requests", "fleet", "slo_alerts", "slo_burn_long",
      "slo_budget_remaining", "fleet_incident_bundles",
      "fleet_bundle_replicas", "fleetobs_kill_switch_parity",
      # goodput & memory attribution (ISSUE 12): the parent fit's
      # decomposition + per-replica device-bytes via the fleet digest
      "goodput", "ledger",
      # data-plane fast path (ISSUE 17): same-run wire A/B (fresh-TCP
      # vs keep-alive vs SHM fast path), cross-caller coalescing under
      # a concurrent same-model burst with full outcome accounting,
      # and the OTPU_FLEET_FASTWIRE=0 bitwise parity pin
      "wire_fresh_p50_ms", "wire_keepalive_p50_ms", "wire_fastpath_p50_ms",
      "wire_keepalive_speedup", "wire_fastpath_speedup",
      "coalesce_merge_factor", "coalesce_members", "coalesce_dispatches",
      "coalesce_sheds", "wire_requests", "wire_ok", "wire_typed_failures",
      "wire_lost", "wire_wrong", "wire_hung", "wire_conn_reuse_pct",
      "wire_conn_stale_retries", "fastwire_kill_switch_parity"}),
    # guarded continuous learning (ISSUE 14): the train-while-serve
    # drill's five arms — continuous beats frozen on the shifted holdout,
    # an injected-drift candidate is rejected typed BEFORE any replica
    # flips, an SLO-tripping candidate auto-rolls back with zero failed
    # requests, a crashed trainer resumes from its checkpoint bitwise,
    # and OTPU_ONLINE=0 restores the frozen serving path
    (["bench.py", "--config", "online"],
     "online_guarded_loop",
     {"auc_frozen", "auc_continuous", "auc_gain", "online_steps",
      "online_examples", "label_join_counts", "trainer_examples_per_s",
      "promotion_outcome", "promotion_version",
      "promotion_failed_requests", "promotion_traffic_requests",
      "promotion_current", "drift_outcome", "drift_error",
      "drift_quarantined", "drift_current_untouched",
      "drift_no_replica_flip", "slo_rollback_outcome",
      "slo_rollback_failed_requests", "slo_rollback_traffic_requests",
      "slo_quarantined", "slo_current_untouched", "trainer_crash_typed",
      "trainer_resumed_from_step", "resume_parity_bitwise",
      "unguarded_ships_bad", "kill_switch_parity",
      "kill_switch_log_empty", "kill_switch_cycle",
      "quarantined_versions", "baseline_value", "baseline_note"}),
    # multihost A/B (ISSUE 18): 1-process vs a REAL N-process gang
    # data-parallel streaming fit, the OTPU_MULTIHOST=0 bitwise
    # kill-switch pin, and the SIGKILL-one-host drill (typed detection,
    # gang restart, 0 lost work, bitwise resumed theta)
    (["bench.py", "--config", "multihost"],
     "multihost_agg_replay_rows_per_sec",
     {"multihost_mode", "multihost_hosts_n",
      "chunk_rows_per_host", "steps_per_epoch",
      "replay_rows_per_s_1p", "replay_rows_per_s_np", "multihost_scaling",
      "theta_max_abs_diff", "multihost_parity_bitwise",
      "kill_switch_parity", "goodput", "ledger", "multihost_hosts",
      "drill_procs", "drill_hosts_lost", "drill_gang_restarts",
      "drill_resume_parity_bitwise", "drill_resumed_from_step",
      "drill_lost_work_steps"}),
    (["bench.py", "--config", "overload"],
     "overload_admission_p99_bound_factor",
     {"p99_ms_admitted", "p99_ms_raw", "p99_bound_factor", "sheds",
      "typed_sheds", "shed_fraction", "completed", "hung_futures",
      "lost_futures", "goodput_rows_per_s_per_chip", "legacy_unbounded",
      "breaker_readmitted", "brownout_level_reached",
      # ISSUE 9: shed anomalies auto-write flight bundles, and every
      # burst request carried a trace id
      "traced_requests", "trace_coverage", "flight_bundles_written"}),
    # multi-tenant control plane (ISSUE 20): the weighted-fair tenancy
    # A/B (same-run 2-tenant skewed burst, unfair vs weighted-fair with
    # the light tenant's p99 bounded and the burster shedding typed),
    # the digest-driven autoscale drill over a REAL fleet (grow under
    # load, drain to min with zero failed trickle requests), and the
    # OTPU_TENANCY=0 + OTPU_AUTOSCALE=0 parity pin
    (["bench.py", "--config", "tenancy"],
     "tenancy_fairness_p99_bound_factor",
     {"fairness_p99_bound_factor", "fairness_retried",
      "fairness_p99_bound_factor_first", "light_p99_ms_unfair",
      "light_p99_ms_fair", "heavy_typed_sheds", "heavy_completed_fair",
      "light_completed_fair", "completed", "hung", "lost",
      "autoscale_peak_replicas", "autoscale_final_replicas",
      "autoscale_min_replicas", "autoscale_max_replicas",
      "autoscale_decisions", "autoscale_decision_log", "autoscale_state",
      "autoscale_scaledown_failures", "autoscale_scaledown_trickle_ok",
      "autoscale_load_failures", "autoscale_load_hung",
      "elasticity_factor", "tenancy_kill_switch_parity"}),
])
def test_harness_emits_one_parseable_line(argv, metric, extra_keys):
    r = _run(argv)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.splitlines()
             if ln.startswith("{") and '"metric"' in ln]
    assert len(lines) == 1, r.stdout
    d = json.loads(lines[0])
    assert d["metric"] == metric
    assert isinstance(d["value"], (int, float)) and d["value"] > 0
    assert d["unit"]
    assert "vs_baseline" in d
    # every record names the device jax reported (pinned to cpu above;
    # conftest's XLA_FLAGS give it 8 virtual devices)
    assert d["platform"] == "cpu" and d["device_kind"] == "cpu"
    assert d["device_count"] == 8
    assert "backend" not in d
    missing = extra_keys - set(d)
    assert not missing, f"contract fields missing: {missing}"
    if "baseline_note" in extra_keys:
        # provenance is a real derivation note, not a placeholder; when a
        # numeric baseline backs vs_baseline the two must be consistent
        assert isinstance(d["baseline_note"], str) and len(d["baseline_note"]) > 40
        if d.get("baseline_value") and d.get("vs_baseline") is not None:
            assert d["vs_baseline"] == round(
                d["value"] / d["baseline_value"], 3)
    if "optim_update" in extra_keys:
        from orange3_spark_tpu.optim.sparse import OPTIM_UPDATES

        assert d["optim_update"] in OPTIM_UPDATES
        assert d["sparse_lowering"] in ("sort", "none")
    if "cache_dtype" in extra_keys:
        from orange3_spark_tpu.io.codec import CACHE_DTYPES

        assert d["cache_dtype"] in CACHE_DTYPES
        if d["cache_dtype"] == "packed" and d.get("compression_ratio"):
            # layout-determined at the criteo layout and 2^22 dims: 160 B
            # a row in f32 against 99 packed (u8 label, 13 x bf16, 26 x 22
            # bits in 18 words) — read 1.616 (CPU, PR 30)
            assert d["compression_ratio"] == 1.616, d["compression_ratio"]
    if argv[0] == "bench.py":
        # every bench.py config embeds the full metrics-registry snapshot
        # (obs/ subsystem) so banked records are self-diagnosing
        assert isinstance(d.get("obs"), dict) and d["obs"], "obs key missing"
        assert "otpu_dispatches_total" in d["obs"]
        for name, m in d["obs"].items():
            assert m["type"] in ("counter", "gauge", "histogram"), name
            assert isinstance(m["values"], list), name
    if "obs_overhead_pct" in extra_keys:
        # the ISSUE-7 arm ran: spans+registry on vs the OTPU_OBS=0 arm of
        # the SAME run (the < 2% criterion is a chip measurement)
        assert d["obs_overhead_pct"] is not None
        assert d["pure_step_ms_obs"] and d["pure_step_ms_obs"] > 0
    if "prof_overhead_pct" in extra_keys:
        # the ISSUE-12 criteria, semantics not just schema: the goodput
        # fractions PARTITION the fit wall (sum 1.0 ± 0.02, contract-
        # gated), the ledger's cache entry agrees with the legacy
        # cache_bytes key within 1%, and the same-run OTPU_PROF on/off
        # step A/B arm ran (its < 2% criterion is a chip measurement)
        gp = d["goodput"]
        assert isinstance(gp, dict) and gp["fractions"], gp
        s = sum(gp["fractions"].values())
        assert abs(s - 1.0) <= 0.02, gp["fractions"]
        assert set(gp["fractions"]) == {
            "device_compute", "input_wait", "host_encode", "sync_wait",
            "framework"}
        assert gp["bottleneck"] in (
            "input_bound", "compute_bound", "sync_bound",
            "framework_bound")
        led = d["ledger"]
        assert isinstance(led, dict) and isinstance(led["owners"], dict)
        if d.get("cache_bytes") and led.get("cache_entry_bytes"):
            rel = abs(led["cache_entry_bytes"] - d["cache_bytes"]) \
                / d["cache_bytes"]
            assert rel <= 0.01, (led["cache_entry_bytes"],
                                 d["cache_bytes"])
        assert d["prof_overhead_pct"] is not None
        assert d["pure_step_ms_prof"] and d["pure_step_ms_prof"] > 0
    if "parity_bitwise" in extra_keys:
        # the resilience claims, not just the schema: injected faults were
        # absorbed (retries happened, output bitwise-identical) and the
        # wedged dispatch raised typed instead of hanging
        assert d["parity_bitwise"] is True
        assert d["watchdog_raised"] is True
        assert d["faults_injected"] >= 1 and d["retries"] >= 1
    if "trace_coverage" in extra_keys:
        # the ISSUE-9 coverage claim: every request through the measured
        # serving window minted a trace id at entry (traced/requests == 1)
        assert d["traced_requests"] >= 1
        assert d["trace_coverage"] == 1.0, (
            d["traced_requests"], d["requests"])
        assert isinstance(d["flight_bundles_written"], int)
    if "scaling_factor" in extra_keys:
        # the fleet claims (ISSUE 10 acceptance), semantics not just
        # schema: N replicas scale aggregate throughput >= 2.5x the
        # single-replica arm on the same burst; EWMA-p95 hedging holds
        # p99 to <= 0.5x the unhedged arm under one injected straggler;
        # the SIGKILL-mid-burst arm loses and hangs NOTHING (failover
        # completes or fails typed) and the supervisor+breaker re-admit
        # the replacement; the rolling version swap fails zero requests
        # and the poisoned version auto-rolls back; the kill-switch arm
        # served bitwise-identically on the single-process path
        assert d["scaling_factor"] >= 2.5, (
            d["scaling_factor"], "first measurement:",
            d.get("scaling_factor_first"))
        if d.get("scaling_retried"):
            # a retried gate must log WHY it retried
            assert d["scaling_factor_first"] is not None
            assert d["scaling_factor_first"] < 2.5
        assert d["hedged_p99_ratio"] <= 0.5, (
            d["p99_ms_hedged"], d["p99_ms_unhedged"])
        assert d["hedges_issued"] >= 1
        assert d["kill_hung"] == 0 and d["kill_lost"] == 0
        assert d["kill_wrong_results"] == 0
        assert (d["kill_completed"] + d["kill_typed_failures"]
                == d["kill_requests"])
        assert d["replica_restarted"] is True
        assert d["killed_replica_readmitted"] is True
        assert d["rollout_outcome"] == "completed"
        assert d["rollout_failed_requests"] == 0
        assert d["rollback_outcome"] == "rolled_back"
        assert d["rollback_current_untouched"] is True
        assert d["kill_switch_local_parity"] is True
        # fleet telemetry plane (ISSUE 11 acceptance): the collector A/B
        # arm ran (its < 2% criterion is not a CPU gate), every replica
        # scraped fresh with the
        # per-replica rpc counters summing across the fleet, the
        # injected-overload SLO drill paged and wrote EXACTLY ONE
        # rate-limited fleet incident bundle carrying every live
        # replica's flight pull, and OTPU_FLEETOBS=0 served bitwise on
        # the bare PR-10 path
        assert d["collector_overhead_pct"] is not None
        assert d["scrape_stale_replicas"] == 0
        assert d["fleet_agg_rpc_requests"] >= d["requests"]
        assert isinstance(d["fleet"], dict) and d["fleet"]["replicas"]
        assert d["slo_alerts"] >= 1
        assert d["slo_burn_long"] >= 14.4   # past the paging threshold
        assert d["fleet_incident_bundles"] == 1
        assert d["fleet_bundle_replicas"] == d["replicas"]
        assert d["fleetobs_kill_switch_parity"] is True
        # ISSUE 12: the parent fit's goodput decomposition rides the
        # fleet record, and the digest carried every replica's
        # per-owner device bytes (the serving executables named)
        gp = d["goodput"]
        assert isinstance(gp, dict) and abs(
            sum(gp["fractions"].values()) - 1.0) <= 0.02
        led = d["ledger"]
        assert len(led["replicas"]) == d["replicas"]
        assert any("serve_executables" in dev
                   for dev in led["replicas"].values()), led["replicas"]
        # data-plane fast path (ISSUE 17 acceptance), semantics not just
        # schema: keep-alive + SHM + coalescing hold small-predict p50
        # to <= 1/3 of the fresh-TCP wire on the same run; the coalescer
        # merged >= 2 members per wire dispatch under the concurrent
        # burst with nothing lost or hung; OTPU_FLEET_FASTWIRE=0 served
        # bitwise on the legacy one-connection-per-request wire
        assert d["wire_fastpath_speedup"] >= 3.0, (
            d["wire_fresh_p50_ms"], d["wire_fastpath_p50_ms"])
        assert d["coalesce_merge_factor"] >= 2.0, d["coalesce_merge_factor"]
        assert d["coalesce_dispatches"] >= 1
        assert d["wire_lost"] == 0 and d["wire_hung"] == 0
        assert d["wire_wrong"] == 0
        assert (d["wire_ok"] + d["wire_typed_failures"]
                == d["wire_requests"])
        assert d["wire_conn_reuse_pct"] > 50.0, d["wire_conn_reuse_pct"]
        assert d["fastwire_kill_switch_parity"] is True
    if "workflow_fused_speedup" in extra_keys:
        # the whole-workflow serving claims (r8 acceptance), semantics
        # not just schema: the fused DAG executable serves >= 2x faster
        # than the stage-by-stage kill-switch path on the same warmed
        # process; a fused request dispatches EXACTLY ONCE while the
        # staged arm pays one dispatch per stage; both arms agree to
        # float tolerance (XLA cross-stage fusion reorders float ops, so
        # bitwise is reserved for same-code-path comparisons); and the
        # staged fit/transform claims the bench_suite config carried
        # still hold in the promoted config
        assert d["workflow_fused_speedup"] >= 2.0, (
            d["workflow_fused_speedup"], "first measurement:",
            d.get("workflow_fused_speedup_first"))
        if d.get("workflow_ab_retried"):
            assert d["workflow_fused_speedup_first"] is not None
            assert d["workflow_fused_speedup_first"] < 2.0
        assert d["dispatch_fused"] == 1, d["dispatch_fused"]
        assert d["dispatch_staged"] == d["workflow_n_stages"] == 3
        assert d["workflow_parity"] is True
        assert d["staged_speedup"] > 0 and d["fit_staged_speedup"] > 0
        # the one-pass streaming moments agree with the in-memory fit
        assert d["streaming_scaler_max_abs_diff"] <= 1e-3, (
            d["streaming_scaler_max_abs_diff"])
        assert d["streaming_fit_s"] > 0
    if "promotion_outcome" in extra_keys:
        # the continuous-learning claims (ISSUE 14 acceptance), semantics
        # not just schema. (1) learning: the continuously-trained
        # candidate beats the frozen serving model on the same-run
        # shifted holdout, and its guarded promotion completed under
        # live traffic with zero failed requests;
        assert d["auc_continuous"] > d["auc_frozen"], (
            d["auc_continuous"], d["auc_frozen"])
        assert d["online_steps"] >= 1
        assert d["label_join_counts"]["joined"] >= 1
        assert d["promotion_outcome"] == "completed"
        assert d["promotion_failed_requests"] == 0
        assert d["promotion_traffic_requests"] >= 1
        assert d["promotion_current"] == d["promotion_version"]
        # (2) drift gate: the injected-drift candidate was rejected
        # TYPED and quarantined before any replica flipped — CURRENT
        # and every replica's served version untouched;
        assert d["drift_outcome"] == "rejected_drift"
        assert "DriftDetectedError" in d["drift_error"]
        assert d["drift_quarantined"] is True
        assert d["drift_current_untouched"] is True
        assert d["drift_no_replica_flip"] is True
        # (3) canary/SLO gate: the bad-but-plausible candidate tripped
        # the burn-rate engine mid-roll and auto-rolled back with zero
        # failed requests, landing in quarantine;
        assert d["slo_rollback_outcome"] == "rolled_back"
        assert d["slo_rollback_failed_requests"] == 0
        assert d["slo_quarantined"] is True
        assert d["slo_current_untouched"] is True
        # (4) crash/resume: the injected trainer death was typed and the
        # resumed trainer converged bitwise to the uninterrupted run;
        assert d["trainer_crash_typed"] is True
        assert d["trainer_resumed_from_step"] >= 1
        assert d["resume_parity_bitwise"] is True
        # (5) the drills mean something: the unguarded loop DOES ship
        # the bad candidate, and OTPU_ONLINE=0 is bitwise-frozen serving
        assert d["unguarded_ships_bad"] is True
        assert d["kill_switch_parity"] is True
        assert d["kill_switch_log_empty"] is True
        assert d["kill_switch_cycle"] == "disabled"
        assert len(d["quarantined_versions"]) >= 2
    if "p99_bound_factor" in extra_keys:
        # the overload claims (ISSUE 8 acceptance): under the injected
        # overload trace the admission-controlled arm keeps p99 >= 3x
        # better than the raw (legacy unbounded) arm, sheds with TYPED
        # errors only, loses/hangs no future, the kill-switch arm
        # reproduced legacy unbounded behavior, the breaker re-admitted
        # the recovered flaky-AOT backend, and the brownout ladder fired
        assert d["p99_bound_factor"] is not None
        assert d["p99_bound_factor"] >= 3.0, d["p99_bound_factor"]
        assert d["sheds"] >= 1 and d["typed_sheds"] >= d["sheds"]
        assert d["completed"] >= 1
        assert d["hung_futures"] == 0 and d["lost_futures"] == 0
        assert d["completed"] + d["sheds"] == d["requests"]
        assert d["legacy_unbounded"] is True
        assert d["breaker_readmitted"] is True
        assert d["brownout_level_reached"] >= 2
        # ISSUE 9: the first shed of the admitted arm auto-wrote a black
        # box (sheds >= 1 is asserted above, so a bundle must exist)
        assert d["flight_bundles_written"] >= 1
    if "fairness_p99_bound_factor" in extra_keys:
        # the control-plane claims (ISSUE 20 acceptance), semantics not
        # just schema. (1) weighted-fair tenancy: on the same-run skewed
        # burst (heavy offers 8x), the light tenant's p99 under the
        # weighted-fair spec is >= 3x tighter than first-come-first-
        # served, the burster's excess sheds TYPED, every light request
        # completes, and nothing hangs or escapes untyped;
        assert d["fairness_p99_bound_factor"] is not None
        assert d["fairness_p99_bound_factor"] >= 3.0, (
            d["fairness_p99_bound_factor"], "first measurement:",
            d.get("fairness_p99_bound_factor_first"))
        if d.get("fairness_retried"):
            # a retried gate must log WHY it retried
            assert (d["fairness_p99_bound_factor_first"] is None
                    or d["fairness_p99_bound_factor_first"] < 3.0)
        assert d["heavy_typed_sheds"] >= 1
        assert d["light_completed_fair"] >= 1
        assert d["hung"] == 0 and d["lost"] == 0
        # (2) elasticity: the digest-driven autoscaler grew the REAL
        # fleet to >= 2 replicas under load, then — load gone, past
        # cooldown — drained back to min via drain-then-stop with ZERO
        # failed requests during scale-down;
        assert d["autoscale_peak_replicas"] >= 2, d["autoscale_peak_replicas"]
        assert d["autoscale_final_replicas"] == d["autoscale_min_replicas"]
        assert d["autoscale_scaledown_failures"] == 0
        assert d["autoscale_scaledown_trickle_ok"] >= 1
        assert d["autoscale_load_failures"] == 0
        assert d["autoscale_load_hung"] == 0
        assert d["autoscale_decisions"] >= 2
        assert d["elasticity_factor"] >= 2.0, d["elasticity_factor"]
        # (3) both kill-switches off is the PR-19 fleet bitwise: a
        # scoped caller changes nothing, no fair-share state is built,
        # and the autoscaler refuses to step
        assert d["tenancy_kill_switch_parity"] is True
    if "multihost_scaling" in extra_keys:
        # the multihost claims (ISSUE 18 acceptance): the N arm is a real
        # N-process gang, theta parity <= 1e-6 between arms, the
        # OTPU_MULTIHOST=0 kill-switch bitwise-identical to the stock
        # path, and the lost-host drill must recover with 0 lost work
        # and a bitwise-resumed theta (the scaling ratio is recorded, not
        # gated: a CPU timing)
        assert d["multihost_mode"] == "multiprocess"
        assert d["multihost_hosts_n"] >= 2
        assert d["multihost_scaling"] > 0
        assert d["theta_max_abs_diff"] <= 1e-6, d["theta_max_abs_diff"]
        assert d["multihost_parity_bitwise"] is True
        assert d["kill_switch_parity"] is True
        # per-host goodput/ledger attribution folded through the digest
        assert d["multihost_hosts"], "per-host attribution missing"
        for h in d["multihost_hosts"].values():
            assert "goodput" in h and "device_memory" in h
        # the drill: >= 1 host lost TYPED, gang restarted, resume at the
        # exact snapshot (0 lost steps) converging bitwise
        assert d["drill_hosts_lost"] >= 1
        assert d["drill_gang_restarts"] >= 1
        assert d["drill_resume_parity_bitwise"] is True
        assert d["drill_lost_work_steps"] == 0


def test_bench_never_reroutes():
    """A platform that cannot initialise is an error, not a slower success:
    no CPU fall-back, no retry in a child, no metric line."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cuda"     # no such backend in this image
    r = subprocess.run(
        [sys.executable, "bench.py", "--rows", "30000", "--epochs", "2"],
        capture_output=True, text=True, timeout=300, cwd=REPO, env=env)
    assert r.returncode != 0, r.stdout[-2000:]
    assert '"metric"' not in r.stdout, r.stdout[-2000:]
