"""Reviewed findings that stay in the tree on purpose.

Every entry MUST carry a reason string explaining why the finding is
acceptable — ``rtpu check`` fails on an entry with an empty reason, and
prints a note for entries that no longer match anything (so stale
suppressions get pruned instead of accreting).
"""

from __future__ import annotations

from ray_tpu._private.staticcheck.common import Allow

ALLOWLIST: list[Allow] = [
    # -- locks ---------------------------------------------------------
    Allow("locks/blocking-under-mutex", "ray_tpu/native/core_worker.cc",
          "send_all() while holding send_mu",
          reason="send_mu exists precisely to serialize frame writers on "
                 "one connection fd; holding it across send_all is the "
                 "design (one mutex per connection, contenders are other "
                 "submitters on the same channel, and a hand-off queue "
                 "would add a copy plus a thread)."),
    Allow("locks/blocking-under-mutex", "ray_tpu/native/shm_store.cc",
          "while holding mu_",
          reason="spill/restore disk IO runs under the store mutex on "
                 "purpose (documented at SpillLocked): eviction and "
                 "restore are the slow path, and serializing them keeps "
                 "spill/create/restore races trivially correct — extent "
                 "reuse must be atomic with the spill that frees it."),
    # -- purity --------------------------------------------------------
    Allow("purity/host-sync-unbracketed", "ray_tpu/train/gbdt.py",
          "np.asarray",
          reason="CPU-only dataset assembly from Python row dicts at "
                 "training setup; there are no device arrays in the GBDT "
                 "path, so this is a plain host copy, not a sync."),
    Allow("purity/host-sync-unbracketed", "ray_tpu/llm/batch.py",
          "np.asarray",
          reason="host-side token-list padding over Python lists before "
                 "device upload; nothing device-resident is involved."),
    Allow("purity/host-sync-unbracketed", "ray_tpu/llm/engine.py",
          "np.asarray",
          reason="the engine samples on host by design: pulling logits "
                 "(and KV pages during migration) to numpy is its single "
                 "designed device sync per decode step, accounted by the "
                 "engine's own step timing rather than a GoodputTracker "
                 "bracket (serving, not training)."),
    Allow("purity/host-sync-unbracketed", "ray_tpu/llm/paged_cache.py",
          "np.asarray",
          reason="hashes host-side token lists (Python ints) to build "
                 "prefix-cache keys; a host copy, not a device sync."),
    # -- shard ---------------------------------------------------------
    Allow("shard/dead-logical-axis", "ray_tpu/parallel/sharding.py",
          "rule 'stage'",
          reason="'stage' is the documented logical spelling for USER-"
                 "supplied pipeline params_specs: pipeline_apply maps "
                 "caller-provided specs through to_partition_spec, so the "
                 "rule is exercised by callers, not by in-tree model "
                 "specs (no in-tree model is pipeline-staged yet)."),
    Allow("shard/comm-axis-unmodeled", "ray_tpu/parallel/sharding.py",
          "mesh axis 'ep'",
          reason="expert parallelism moves tokens by all-to-all, not by "
                 "the ring collectives comm.estimate_train_comm models; "
                 "comm.py's docstring scopes 'ep' out on purpose until "
                 "the estimator grows an a2a cost term."),
    Allow("shard/comm-axis-unmodeled", "ray_tpu/parallel/sharding.py",
          "mesh axis 'pp'",
          reason="pipeline stages talk via ppermute point-to-point "
                 "activations, not ring collectives; comm.py documents "
                 "'pp' as intentionally outside the estimator's model."),
    # -- proto ---------------------------------------------------------
    Allow("proto/opcode-uncalled", "ray_tpu/_private/wire_constants.py",
          "XFER_PULL is dispatched",
          reason="mixed-version compat: peers predating XFER_PULL_RANGE "
                 "striping still send plain XFER_PULL, so the daemon "
                 "keeps the dispatch case while current code always "
                 "sends ranged pulls; drop with the next protocol bump."),
    Allow("proto/chaos-lane-off", "ray_tpu/_private/direct.py",
          "RTPU_TESTING_RPC_FAILURE",
          reason="known gap, tracked as ROADMAP item 1: RPC chaos "
                 "injects at the Python frame layer, which the C++ "
                 "transport bypasses by construction, so direct.py must "
                 "switch the native lane off for the flag to bite at "
                 "all; native-lane chaos hooks land with the C++ "
                 "submission-path migration."),
    # -- metrics: families consumed generically, not by literal name ----
    # metrics/family-unconsumed only sees literal name mentions; these
    # families ARE consumed — every registered family rides the /metrics
    # exposition, `rtpu top`'s TSDB overview, and /api/timeseries, all of
    # which enumerate families dynamically.  Entries are scoped by name
    # prefix so a future family in the same file outside the prefix still
    # gets a fresh look.
    Allow("metrics/family-unconsumed", "ray_tpu/llm/engine.py", "'llm_",
          reason="engine telemetry (slots/pages/prefix-cache/KV-tier "
                 "counters) judged via the dynamic surfaces: rtpu top "
                 "rates, /metrics scrape, and ad-hoc SLO rules like "
                 "p90(llm_queue_wait_s, 5m); the serving SLO that pages "
                 "(llm_ttft_p90) names its family explicitly."),
    Allow("metrics/family-unconsumed", "ray_tpu/core/store_client.py",
          "'store_",
          reason="store dataplane counters (puts/gets/transfer bytes + "
                 "latency, reconnects) exist for rtpu top rate rows and "
                 "BENCH harness scrapes; no fixed rule names them because "
                 "healthy thresholds are workload-dependent."),
    Allow("metrics/family-unconsumed", "ray_tpu/_private/node.py",
          "'store_daemon_restarts_total'",
          reason="the restart signal's judged surface is the event plane "
                 "(store.daemon_restart events, asserted in "
                 "test_tsdb_slo); the counter is the scrapeable shadow "
                 "for external Prometheus alerting."),
    Allow("metrics/family-unconsumed", "ray_tpu/_private/scheduler.py",
          "'scheduler_",
          reason="scheduler depth/dispatch/spill counters back rtpu top "
                 "and the queue-wait SLO family "
                 "(scheduler_task_queue_wait_s) which IS named by rules; "
                 "the siblings stay for dynamic-surface triage."),
    Allow("metrics/family-unconsumed", "ray_tpu/_private/data_service.py",
          "'data_job_",
          reason="per-job cache/failover/worker gauges are tagged by job "
                 "name and read through rtpu top's by-tag rate splits; a "
                 "literal-name consumer would hardcode one job."),
    Allow("metrics/family-unconsumed", "ray_tpu/serve/replica.py",
          "'serve_",
          reason="replica-local latency/ongoing gauges feed the "
                 "autoscaler's queue_len probes and the /metrics scrape; "
                 "the serve SLO families named by DEFAULT_RULES "
                 "(serve_errors_total/serve_requests_total) cover the "
                 "paging story."),
    Allow("metrics/family-unconsumed",
          "ray_tpu/serve/request_router/base.py", "'serve_",
          reason="router imbalance/prefix-hit gauges are rtpu serve / "
                 "rtpu top diagnostics for routing-policy comparisons; "
                 "thresholds are policy-dependent so no fixed rule names "
                 "them."),
    Allow("metrics/family-unconsumed", "ray_tpu/util/goodput.py",
          "'train_",
          reason="step-anatomy shadows of the goodput report "
                 "(compile_s/tflops/restarts); the judged family "
                 "(train_goodput_fraction) is named by the train_goodput "
                 "default rule, the rest back rtpu top drill-down."),
    Allow("metrics/family-unconsumed",
          "ray_tpu/_private/object_transfer.py", "'transfer_",
          reason="range-striping byte/latency histograms for rtpu top "
                 "and transfer benchmarks; no fixed threshold exists — "
                 "healthy values scale with object sizes."),
]
