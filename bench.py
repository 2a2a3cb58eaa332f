"""Benchmark: MD5 proof-of-work search throughput on the local accelerator.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": "MH/s", "vs_baseline": N}

* ``value``: sustained device throughput (MH/s/chip) of the SERVING path —
  the layout-keyed dynamic search step exactly as a booted worker
  dispatches it (ops/search_step.py cached regime, launch multiplier
  included) at difficulty 8 nibbles (32 bits, BASELINE.md config 4's
  difficulty) on width-4 chunks.  Static-compiled and Pallas rates go to
  stderr for comparison.
* ``vs_baseline``: ratio against a single CPU worker-equivalent — the
  native C++ miner at one thread (a strictly-faster stand-in for the
  reference's single-goroutine Go worker, BASELINE.md config 1; the Go
  loop also pays per-candidate hex formatting, worker.go:354-355, so this
  baseline is conservative).

Details go to stderr; only the JSON line goes to stdout.

Stages execute in strict information-value order so a run cut short
at any point costs only the tail, never the registry's standing —

  A. md5 headline (serving / xla-static / pallas)
  B. every other model's PRODUCTION path (the Pallas kernel a TPU
     config actually serves) — the whole registry lands here
  C. anchors: measured VPU roofline + native CPU baselines
  D. e2e wall-clock solves (deadline-gated)
  E. diagnostic XLA serving lines, HBM-bound ones budget-capped from
     their last measured rate, sha512/sha384 skipped outright
     (compile-impractical, docs/KERNELS.md) — deadline-gated

A family of CPU-only stages rides after the device phases (each also
standalone via ``--control-plane`` /
``--load-slo`` / ``--membership`` / ``--forensics-overhead`` /
``--cluster-scale`` / ``--cache-ha`` / ``--soak`` /
``--mesh-serving``):
the RPC control-plane latency stage, the open-loop load +
cluster-SLO stage (achieved
solves/s and cluster-merged p95 under seeded Poisson traffic, judged
against config/slo.json), the elastic-membership stage (ISSUE 12:
lease-expiry reassignment + straggler hedging), the
forensics-overhead stage (ISSUE 14: serving solves/s with
spans+exemplars on vs off, 5% bound asserted), the coordinator
scale-out stage (ISSUE 15), the cache-HA stage (ISSUE 16), and the
soak-overhead stage (ISSUE 18: retention-sweep cost as a pct of
sweeps-off throughput, interleaved arms, 5% bound asserted), and the
mesh-serving scale stage (ISSUE 20: scheduler solves/s at 4 vs 1
virtual CPU devices through the lane planner's mesh lane, >= 2x
asserted) — the perf rows that need no chip.

Every reading is screened against ``last_measured.json``: a rate
deviating more than 3x from the previous measurement of the same stage
is flagged as suspect (earlier runs produced such readings — the
ripemd160 69-vs-2421 MH/s and sha3 0.85-vs-6.3 MH/s cases) and does NOT
replace the provenance value; it is recorded under ``suspect_readings``
in both the JSON line and the provenance file instead.
"""

from __future__ import annotations

import json
import os
import queue
import sys
import time

# stdlib-only (the runtime layer has no jax dependency), so importing it
# eagerly keeps the CPU-only stages jax-free
from distpow_tpu.runtime.watchdog import FIRST_COMPILE_GRACE_S, WATCHDOG

# Checked-in provenance for the last successful hardware measurement
# (VERDICT r3 item 2): a partial (hung) run degrades to this instead of a bare
# 0.0, and every successful run refreshes it, so the headline number is
# always backed by a file in the repo rather than prose.
# BENCH_LAST_MEASURED_PATH redirects BOTH the read and the write — the
# CI bench rehearsal (scripts/ci.sh --bench-rehearsal) exercises the
# whole provenance plumbing against a temp file so a CPU pass can
# never contaminate the hardware provenance.
_LAST_MEASURED_PATH = os.environ.get("BENCH_LAST_MEASURED_PATH") or \
    os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "docs", "artifacts", "last_measured.json",
    )

# md5 paths carry bare labels; every other model's lines are
# "<model>-<path>".
MD5_LABELS = ("serving", "xla-static", "pallas")

# Registry models beyond md5, in bench order.
OTHER_MODELS = ("sha256", "sha1", "ripemd160", "sha512", "sha384",
                "sha3_256", "blake2b_256", "sha256d")

# Serving steps whose loop form re-stacks state every round and lands
# HBM-bound at single-digit MH/s (docs/KERNELS.md): their diagnostic
# lines get a rate-derived candidate budget instead of the shared 2^28.
HBM_BOUND_SERVING = ("sha3_256", "blake2b_256")

# Anomaly screen: a reading more than this factor away from the last
# measured value for the same stage is suspect (see module docstring).
ANOMALY_TOLERANCE = 3.0


def _read_last_measured():
    try:
        with open(_LAST_MEASURED_PATH) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def screen_rates(measured_mhs: dict, last_measured: dict | None,
                 tolerance: float = ANOMALY_TOLERANCE):
    """Screen per-stage rates (MH/s) against the previous measurement.

    Returns ``(accepted, suspect)``: ``accepted`` is what goes into the
    provenance file's ``rates_mhs`` — the measured value normally, but
    the PREVIOUS value where the new reading deviates by more than
    ``tolerance`` x in either direction (a degraded-device transient or
    a sync-artifact inflation; both have produced real bogus readings,
    and neither should silently become the registry's standing).
    ``suspect`` records each flagged reading with its context so the
    anomaly is visible in the JSON rather than buried in stderr.

    ``BENCH_ACCEPT_ANOMALIES=1`` bypasses the screen (for a deliberate
    re-measurement after a code change that legitimately moved a rate).
    """
    prev = (last_measured or {}).get("rates_mhs") or {}
    accept_all = os.environ.get("BENCH_ACCEPT_ANOMALIES") == "1"
    accepted: dict = {}
    suspect: dict = {}
    for lbl, v in measured_mhs.items():
        p = prev.get(lbl)
        if (not accept_all and p and p > 0 and v > 0
                and (v > p * tolerance or v * tolerance < p)):
            suspect[lbl] = {
                "measured_mhs": round(v, 2),
                "last_measured_mhs": round(p, 2),
                "ratio": round(v / p, 4),
            }
            accepted[lbl] = p
        else:
            accepted[lbl] = round(v, 2)
    return accepted, suspect


def finalize_record(rates_hs: dict, last_measured: dict | None,
                    baseline_hs: float | None, note: str | None = None,
                    control_plane: dict | None = None,
                    serving_loop: dict | None = None,
                    load_slo: dict | None = None,
                    membership: dict | None = None,
                    forensics: dict | None = None,
                    cluster_scale: dict | None = None,
                    cache_ha: dict | None = None,
                    soak: dict | None = None,
                    mesh_serving: dict | None = None):
    """Build the stdout JSON line and the provenance record, once.

    Shared by the success path and the hang bailout (review r5: two
    slightly-divergent copies of this logic is how exactly one of them
    ended up missing the anomaly screen).  Rules:

    * every stage is screened against ``last_measured`` (see
      ``screen_rates``);
    * the md5 headline path is selected on the SCREENED values, so an
      inflated suspect reading can neither steal the path selection nor
      smuggle its stale previous value in as the headline;
    * the stdout ``value`` is the honest measurement of the selected
      path (flagged if suspect); the provenance ``value`` obeys the
      screen;
    * stages present in the previous provenance but not measured this
      run are carried forward under an explicit ``carried_forward``
      list — absence of the marker means measured-this-run (review r5:
      a bare merge made stale values indistinguishable from fresh ones
      under the new date/run_id).

    With no md5 label in ``rates_hs`` (the device hung before Phase A
    produced one) the device-hung-shaped line is returned instead of
    crashing on ``max`` over an empty pool (advisor r5 low #3 — the
    hang bailout guards this case itself, but main()'s final call
    relied on Phase A's unguarded serving stage crashing first).  The
    provenance half is None in that case: a run that measured no md5
    stage must not overwrite last_measured.json with a zero record
    (the pre-guard crash at least left provenance intact).
    """
    measured_mhs = {l: v / 1e6 for l, v in rates_hs.items()}
    accepted, suspect = screen_rates(measured_mhs, last_measured)
    # suspect rows pending a clean re-measure (VERDICT r4 item 3 /
    # ISSUE 6): a reading the screen rejected stays ANNOTATED — in the
    # provenance's suspect_readings AND a suspect_rows list both
    # artifacts carry — until a run re-measures that stage clean.  The
    # provenance value is still the screened previous standing, but it
    # is no longer carried silently.
    pending_suspect = {
        lbl: info
        for lbl, info in
        (((last_measured or {}).get("suspect_readings")) or {}).items()
        if lbl not in measured_mhs or lbl in suspect
    }
    all_suspect = dict(pending_suspect)
    all_suspect.update(suspect)
    md5_acc = {l: v for l, v in accepted.items() if l in MD5_LABELS}
    if not md5_acc:
        if mesh_serving and not (control_plane or serving_loop or load_slo
                                 or membership or forensics or cluster_scale
                                 or cache_ha or soak):
            # a mesh-serving-only run (bench.py --mesh-serving): the
            # ninth CPU perf row (ISSUE 20) — scheduler
            # solves/s speedup of the mesh lane at 4 simulated CPU
            # devices vs 1 (the >=2x floor is asserted inside the
            # stage).  Kernel provenance stays untouched (prov None)
            # like the other CPU-only shapes.
            line = {
                "metric": ("mesh-serving scheduler solves/s speedup, "
                           "4 vs 1 simulated CPU devices "
                           "(CPU)"),
                "value": mesh_serving.get("speedup_x", 0.0),
                "unit": "x",
                "vs_baseline": 0.0,
                "mesh_serving": mesh_serving,
            }
            if note:
                line["note"] = note
            return line, None
        if soak and not (control_plane or serving_loop or load_slo
                         or membership or forensics or cluster_scale
                         or cache_ha):
            # a soak-only run (bench.py --soak): the eighth
            # CPU perf row (ISSUE 18) — retention-sweep
            # overhead as a percentage of sweeps-off throughput over
            # interleaved arms (the <5% bound and the on-arm green
            # verdicts are asserted inside the stage).  Kernel
            # provenance stays untouched (prov None) like the other
            # CPU-only shapes.
            line = {
                "metric": ("soak-plane sweep overhead pct of "
                           "sweeps-off solves/s, interleaved arms "
                           "(CPU)"),
                "value": soak.get("overhead_pct", 0.0),
                "unit": "%",
                "vs_baseline": 0.0,
                "soak": soak,
            }
            if mesh_serving:
                line["mesh_serving"] = mesh_serving
            if note:
                line["note"] = note
            return line, None
        if cache_ha and not (control_plane or serving_loop or load_slo
                             or membership or forensics or cluster_scale):
            # a cache-HA-only run (bench.py --cache-ha): the seventh
            # CPU perf row (ISSUE 16) — repeat-wave
            # cache-hit ratio on the surviving pool after a member
            # kill, replication on vs off (the 1.0-ratio / zero-fanout
            # floors are asserted inside the stage).  Kernel
            # provenance stays untouched (prov None) like the other
            # CPU-only shapes.
            line = {
                "metric": ("cache-HA repeat hit ratio on the survivor "
                           "after a coordinator kill, replication on "
                           "vs off (CPU)"),
                "value": cache_ha.get("hit_ratio_on", 0.0),
                "unit": "ratio",
                "vs_baseline": cache_ha.get("on_vs_off_x", 0.0),
                "cache_ha": cache_ha,
            }
            if soak:
                line["soak"] = soak
            if mesh_serving:
                line["mesh_serving"] = mesh_serving
            if note:
                line["note"] = note
            return line, None
        if cluster_scale and not (control_plane or serving_loop
                                  or load_slo or membership or forensics):
            # a cluster-scale-only run (bench.py --cluster-scale): the
            # sixth CPU perf row (ISSUE 15) — aggregate
            # open-loop solves/s speedup of the largest coordinator
            # pool vs one coordinator (the 1.6x/2.5x acceptance floors
            # are asserted inside the stage).  Kernel provenance stays
            # untouched (prov None) like the other CPU-only shapes.
            speedups = cluster_scale.get("speedup") or {}
            top_key = max(speedups, default=None,
                          key=lambda k: int(k.split("_")[0][1:]))
            top_n = int(top_key.split("_")[0][1:]) if top_key else 0
            line = {
                "metric": (f"cluster-scale aggregate solves/s speedup, "
                           f"{top_n}-coordinator pool vs 1 "
                           "(CPU)"),
                "value": speedups.get(top_key, 0.0) if top_key else 0.0,
                "unit": "x",
                "vs_baseline": 0.0,
                "cluster_scale": cluster_scale,
            }
            if cache_ha:
                line["cache_ha"] = cache_ha
            if soak:
                line["soak"] = soak
            if mesh_serving:
                line["mesh_serving"] = mesh_serving
            if note:
                line["note"] = note
            return line, None
        if forensics and not (control_plane or serving_loop or load_slo
                              or membership):
            # a forensics-only run (bench.py --forensics-overhead): the
            # fifth CPU perf row (ISSUE 14) — serving
            # throughput with spans+exemplars on as a ratio of off
            # (the 5% acceptance bound is asserted inside the stage).
            # Kernel provenance stays untouched (prov None) like the
            # other CPU-only shapes.
            line = {
                "metric": ("forensics overhead: serving solves/s with "
                           "spans+exemplars on, as a ratio of off "
                           "(CPU)"),
                "value": forensics.get("on_vs_off_x", 0.0),
                "unit": "x",
                "vs_baseline": 0.0,
                "forensics": forensics,
            }
            if cluster_scale:
                line["cluster_scale"] = cluster_scale
            if cache_ha:
                line["cache_ha"] = cache_ha
            if soak:
                line["soak"] = soak
            if mesh_serving:
                line["mesh_serving"] = mesh_serving
            if note:
                line["note"] = note
            return line, None
        if membership and not (control_plane or serving_loop or load_slo):
            # a membership-only run (bench.py --membership): the fourth
            # CPU perf row (ISSUE 12) — straggler-round
            # completion with hedging on, one frozen worker out of the
            # fleet, vs the all-healthy round.  Kernel provenance stays
            # untouched (prov None) like the other CPU-only shapes.
            st = membership.get("straggler") or {}
            # a capped hedged round reports the cap as its floor — the
            # headline value must stay NUMERIC (every other bench row
            # guarantees a number; a null would break the consumers)
            hedged = st.get("hedged_s")
            capped = hedged is None
            metric = ("membership straggler round completion s, "
                      "hedging on, 1 frozen of "
                      f"{st.get('n_workers', 4)} workers "
                      "(CPU)")
            if capped:
                metric += "; hedged round hit the measurement cap"
            line = {
                "metric": metric,
                "value": (float(st.get("cap_s") or 0.0) if capped
                          else hedged),
                "unit": "s",
                "vs_baseline": st.get("hedged_vs_healthy_x") or 0.0,
                "membership": membership,
            }
            if forensics:
                line["forensics"] = forensics
            if cluster_scale:
                line["cluster_scale"] = cluster_scale
            if cache_ha:
                line["cache_ha"] = cache_ha
            if soak:
                line["soak"] = soak
            if mesh_serving:
                line["mesh_serving"] = mesh_serving
            if note:
                line["note"] = note
            return line, None
        if load_slo and not control_plane and not serving_loop:
            # a load-slo-only run (bench.py --load-slo): the third
            # CPU perf row (ISSUE 8) — open-loop achieved
            # solves/s at the highest offered rate with the cluster-
            # merged SLO asserted.  Kernel provenance stays untouched
            # (prov None), like the other CPU-only shapes below.
            rows = load_slo.get("rates") or {}
            top = max(rows.values(), key=lambda r: r.get("target_hz", 0.0),
                      default={})
            line = {
                "metric": (
                    "open-loop load harness achieved solves/s at "
                    f"{top.get('target_hz', 0.0):g} req/s offered, "
                    "cluster-merged SLO asserted "
                    "(CPU)"),
                "value": top.get("achieved_solves_per_s", 0.0),
                "unit": "solves/s",
                "vs_baseline": 0.0,
                "load_slo": load_slo,
            }
            if membership:
                line["membership"] = membership
            if forensics:
                line["forensics"] = forensics
            if cluster_scale:
                line["cluster_scale"] = cluster_scale
            if cache_ha:
                line["cache_ha"] = cache_ha
            if soak:
                line["soak"] = soak
            if mesh_serving:
                line["mesh_serving"] = mesh_serving
            if note:
                line["note"] = note
            return line, None
        if serving_loop and not control_plane:
            # a serving-loop-only run (bench.py --serving-loop): the
            # other CPU perf row — blocking host syncs
            # per solve, serial vs persistent (ISSUE 6 acceptance).
            # Kernel provenance stays untouched (prov None).
            line = {
                "metric": ("serving-loop blocking host syncs per solve, "
                           "serial vs persistent driver "
                           "(CPU)"),
                "value": serving_loop.get("syncs_reduction_x", 0.0),
                "unit": "x",
                "vs_baseline": 0.0,
                "serving_loop": serving_loop,
            }
            if load_slo:
                line["load_slo"] = load_slo
            if membership:
                line["membership"] = membership
            if forensics:
                line["forensics"] = forensics
            if cluster_scale:
                line["cluster_scale"] = cluster_scale
            if cache_ha:
                line["cache_ha"] = cache_ha
            if soak:
                line["soak"] = soak
            if mesh_serving:
                line["mesh_serving"] = mesh_serving
            if note:
                line["note"] = note
            return line, None
        if control_plane:
            # a control-plane-only run (bench.py --control-plane):
            # the headline becomes the one perf row that needs no
            # chip — cancel-propagation p95 at 8
            # workers on the production (parallel+binary) path.  Kernel
            # provenance is deliberately untouched (prov None): a run
            # that measured no md5 stage must not re-stamp
            # last_measured.json.
            head = (control_plane.get("cancel", {}).get("n8", {})
                    .get("parallel", {}).get("p95_ms", 0.0))
            line = {
                "metric": ("control-plane cancel fanout->last-ack p95 ms, "
                           "8 workers, parallel fan-out + binary wire "
                           "(CPU)"),
                "value": head,
                "unit": "ms",
                "vs_baseline": control_plane.get(
                    "speedup", {}).get("cancel_p95_n8", 0.0),
                "control_plane": control_plane,
            }
            if serving_loop:
                line["serving_loop"] = serving_loop
            if load_slo:
                line["load_slo"] = load_slo
            if membership:
                line["membership"] = membership
            if forensics:
                line["forensics"] = forensics
            if cluster_scale:
                line["cluster_scale"] = cluster_scale
            if cache_ha:
                line["cache_ha"] = cache_ha
            if soak:
                line["soak"] = soak
            if mesh_serving:
                line["mesh_serving"] = mesh_serving
            if note:
                line["note"] = note
            return line, None
        line = {
            "metric": "MH/s/chip md5 pow search (device hung mid-bench)",
            "value": 0.0,
            "unit": "MH/s",
            "vs_baseline": 0.0,
        }
        if measured_mhs:
            line["rates_mhs"] = {l: round(v, 2)
                                 for l, v in measured_mhs.items()}
        if note:
            line["note"] = note
        return line, None
    # headline selection: prefer md5 paths that measured CLEAN this run
    # — an inflated suspect reading must not steal the selection, and a
    # deflated one must not win it either (its screened value is the
    # stale-high previous standing, but its stdout value would be the
    # degraded measurement; review r5).  Only if every md5 path is
    # suspect does the screened pool decide.
    pool = {l: v for l, v in md5_acc.items() if l not in suspect} or md5_acc
    best_label = max(pool, key=pool.get)
    # the serving path is what a booted worker actually dispatches;
    # report it as headline unless another path is materially (>2%)
    # faster on screened values
    if "serving" in pool and pool[best_label] <= pool["serving"] * 1.02:
        best_label = "serving"
    measured_best = measured_mhs[best_label]
    vs = 0.0
    if baseline_hs:
        vs = round(measured_best * 1e6 / baseline_hs, 2)
    elif (last_measured and last_measured.get("vs_baseline")
          and last_measured.get("value")):
        # value / vs_baseline = baseline MH/s of the provenance run
        vs = round(measured_best
                   / (last_measured["value"] / last_measured["vs_baseline"]),
                   2)
    metric = f"MH/s/chip md5 pow search ({best_label} path, diff=32bits"
    if note:
        metric += f"; {note}"
    metric += ")"
    if best_label in suspect:
        metric += "; headline reading suspect vs last measured"
    line = {
        "metric": metric,
        "value": round(measured_best, 3),
        "unit": "MH/s",
        "vs_baseline": vs,
        # the driver records this stdout line as BENCH_r{N}.json: the
        # per-stage rates measured THIS run ride along so the registry
        # standing is in the round artifact itself, not only in the
        # provenance file (VERDICT r4 item 1's Done criterion).  Values
        # here are the honest measurements (suspect ones are flagged
        # below, and the provenance file carries the screened view);
        # stages not measured this run are absent — never stale.
        "rates_mhs": {l: round(v, 2) for l, v in measured_mhs.items()},
    }
    if suspect:
        line["suspect_readings"] = suspect
    prov = dict(line, rates_mhs=dict(accepted))
    if note:
        prov["note"] = note
    if best_label in suspect:
        prov["value"] = accepted[best_label]
        prov["vs_baseline"] = (
            round(accepted[best_label] * 1e6 / baseline_hs, 2) if baseline_hs
            else (last_measured or {}).get("vs_baseline", 0.0)
        )
    carried = []
    for lbl, v in ((last_measured or {}).get("rates_mhs") or {}).items():
        if lbl not in prov["rates_mhs"]:
            prov["rates_mhs"][lbl] = v
            carried.append(lbl)
    if carried:
        prov["carried_forward"] = sorted(carried)
    if all_suspect:
        prov["suspect_readings"] = all_suspect
        rows = sorted(l for l in all_suspect if l in prov["rates_mhs"])
        if rows:
            # the annotation consumers read: these rates_mhs rows are
            # under question (screened-out reading this run, or a
            # pending re-measure from an earlier one) — the generated
            # registry table footnotes them (gen_registry_table.py)
            prov["suspect_rows"] = rows
            line["suspect_rows"] = rows
    if control_plane:
        # the control-plane row rides both artifacts: the stdout line
        # (the driver's BENCH record) and provenance
        line["control_plane"] = control_plane
        prov["control_plane"] = control_plane
    elif (last_measured or {}).get("control_plane"):
        prov["control_plane"] = last_measured["control_plane"]
    if serving_loop:
        line["serving_loop"] = serving_loop
        prov["serving_loop"] = serving_loop
    elif (last_measured or {}).get("serving_loop"):
        prov["serving_loop"] = last_measured["serving_loop"]
    if load_slo:
        line["load_slo"] = load_slo
        prov["load_slo"] = load_slo
    elif (last_measured or {}).get("load_slo"):
        prov["load_slo"] = last_measured["load_slo"]
    if membership:
        line["membership"] = membership
        prov["membership"] = membership
    elif (last_measured or {}).get("membership"):
        prov["membership"] = last_measured["membership"]
    if forensics:
        line["forensics"] = forensics
        prov["forensics"] = forensics
    elif (last_measured or {}).get("forensics"):
        prov["forensics"] = last_measured["forensics"]
    if cluster_scale:
        line["cluster_scale"] = cluster_scale
        prov["cluster_scale"] = cluster_scale
    elif (last_measured or {}).get("cluster_scale"):
        prov["cluster_scale"] = last_measured["cluster_scale"]
    if cache_ha:
        line["cache_ha"] = cache_ha
        prov["cache_ha"] = cache_ha
    elif (last_measured or {}).get("cache_ha"):
        prov["cache_ha"] = last_measured["cache_ha"]
    if soak:
        line["soak"] = soak
        prov["soak"] = soak
    elif (last_measured or {}).get("soak"):
        prov["soak"] = last_measured["soak"]
    if mesh_serving:
        line["mesh_serving"] = mesh_serving
        prov["mesh_serving"] = mesh_serving
    elif (last_measured or {}).get("mesh_serving"):
        prov["mesh_serving"] = last_measured["mesh_serving"]
    return line, prov


def _write_last_measured(record: dict) -> None:
    """Refresh the provenance file (best-effort; never fails the bench)."""
    import subprocess

    try:
        rev = subprocess.run(
            # the REPO's revision, not the provenance file's directory —
            # BENCH_LAST_MEASURED_PATH may point into a temp dir
            ["git", "-C", os.path.dirname(os.path.abspath(__file__)),
             "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
    except Exception:
        rev = "unknown"
    record = dict(
        record,
        date=time.strftime("%Y-%m-%d %H:%M:%S %z"),
        run_id=f"bench.py@{rev}",
    )
    if os.environ.get("BENCH_NO_WRITE") == "1":
        print("[bench] BENCH_NO_WRITE=1: provenance not refreshed",
              file=sys.stderr)
        return
    try:
        os.makedirs(os.path.dirname(_LAST_MEASURED_PATH), exist_ok=True)
        with open(_LAST_MEASURED_PATH, "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
    except OSError as exc:
        print(f"[bench] could not write last_measured: {exc}",
              file=sys.stderr)


def device_rate(step_builder, label: str, min_seconds: float = 2.0,
                compile_grace: float = FIRST_COMPILE_GRACE_S,
                start_iters: int = 4) -> float:
    """Sustained candidates/sec of a step(chunk0)->uint32 launcher.

    Adaptively scales the launch count until the timed window is at least
    ``min_seconds`` so dispatch jitter can't dominate.
    ``start_iters`` seeds the first timed window — diagnostic stages on
    known-slow paths pass 1 so a single window can't cost 4x the
    per-call time before the budget logic even sees a timing (bench7
    spent 78.7 s inside sha3's first window this way).

    Synchronization: the timed window ends with ``int(last_out)`` — a
    device_get of the final launch's result.  Launches execute FIFO, so
    fetching the last value proves every prior launch completed.
    """
    import jax.numpy as jnp

    # active window + beats: when main() arms the watchdog, a device
    # hang mid-timing converts to the diagnostic JSON line and a
    # non-zero exit instead of hanging the process forever
    with WATCHDOG.active():
        # the first call is ONE uninterruptible compile+sync — it cannot
        # beat, and the biggest graphs (sha512's limb emulation) have
        # out-waited the 420 s window on a HEALTHY device (r4 first
        # bench attempt): widen the window for just this call
        with WATCHDOG.grace(compile_grace):
            step, batch = step_builder()
            int(step(jnp.uint32(1 << 24)))  # compile + real sync

        iters = max(1, start_iters)
        while True:
            WATCHDOG.beat()
            t0 = time.time()
            out = None
            for i in range(iters):
                out = step(jnp.uint32(((1 << 24) + i * batch) & 0xFFFFFFFF))
            sink = int(out)  # forces the whole FIFO of launches to complete
            dt = time.time() - t0
            if dt >= min_seconds or iters >= 1 << 10:
                break
            iters = min(1 << 10, max(iters * 2, int(iters * min_seconds / max(dt, 1e-3)) + 1))
        del sink
    rate = batch * iters / dt
    print(f"[bench] {label}: {rate / 1e6:.2f} MH/s "
          f"({iters} x {batch} candidates in {dt:.3f}s)", file=sys.stderr)
    return rate


def measured_vpu_roofline(min_seconds: float = 2.0) -> float:
    """Measured int32 VPU ceiling (ops/s) at the serving footprint.

    Runs independent uint32 rotate-add chains over a 2^21-element vector
    (the serving sub-batch shape): per link ``y = rotl(y, s) + K`` with
    MD5's own shift/constant tables so nothing folds.  Four independent
    chains per element give the ILP a perfect scheduler could extract;
    the result is therefore a *measured ceiling* for this op mix, not a
    spec number.  Op counting convention matches OPS_PER_HASH: a rotate
    is 3 ops (<<, >>, |) and each add is 1 — so if the hardware fuses
    the rotate the same fusion is available to (and counted for) the
    hash paths, and the utilization ratio stays apples-to-apples.
    (VERDICT r2 weak #4: the old 7.7 Tops/s figure was back-derived
    from the measured rates; this anchors it.)
    """
    import jax
    import jax.numpy as jnp

    from distpow_tpu.models.md5_jax import MD5_K, MD5_S

    n = 1 << 21
    CHAINS = 4
    LINKS = 64
    OPS_PER_LINK = 4  # <<, >>, |, +

    @jax.jit
    def run(seed, reps):
        x = jax.lax.broadcasted_iota(jnp.uint32, (n,), 0) + seed
        chains = tuple(
            x + jnp.uint32((i * 0x9E3779B9) & 0xFFFFFFFF) for i in range(CHAINS)
        )

        def body(_, chains):
            out = []
            for ci, y in enumerate(chains):
                for j in range(LINKS):
                    s = MD5_S[(j + 17 * ci) % len(MD5_S)]
                    y = ((y << s) | (y >> (32 - s))) + jnp.uint32(MD5_K[j])
                out.append(y)
            return tuple(out)

        chains = jax.lax.fori_loop(0, reps, body, chains)
        acc = chains[0]
        for y in chains[1:]:
            acc = acc ^ y
        return acc[0]

    with WATCHDOG.active():
        int(run(jnp.uint32(1), 1))  # compile + sync
        reps = 64
        while True:
            WATCHDOG.beat()
            t0 = time.time()
            sink = int(run(jnp.uint32(2), reps))
            dt = time.time() - t0
            if dt >= min_seconds or reps >= 1 << 20:
                break
            reps = max(reps * 2, int(reps * min_seconds / max(dt, 1e-3)) + 1)
        del sink
    rate = n * reps * CHAINS * LINKS * OPS_PER_LINK / dt
    print(f"[bench] measured VPU int32 roofline: {rate / 1e12:.2f} Tops/s "
          f"({CHAINS} chains x {LINKS} rotl+add links x {reps} reps over "
          f"2^21 lanes in {dt:.3f}s)", file=sys.stderr)
    return rate


def _cp_percentile(sorted_vals, q: float) -> float:
    """Nearest-rank percentile over an already-sorted sample list."""
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, max(0, round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


def control_plane_stage(ns=(2, 8, 32), rounds=8, delay_ms=40.0) -> dict:
    """Control-plane latency stage (``--control-plane``): CPU-only,
    in-process cluster, no chip needed (ISSUE 5).

    Measures fanout->first-result and cancel fanout->last-ack p50/p95
    at N workers, serial-vs-parallel fan-out and json-vs-binary wire,
    straight from the coordinator's own flight-recorder events
    (``coord.first_result`` / ``coord.cancel_complete`` carry the
    per-round latencies the PR-3 histograms aggregate).  A deterministic
    server-side delay fault (runtime/faults.py) of ``delay_ms`` on every
    worker Mine/Found models the per-RPC service latency a localhost
    loop otherwise hides: the serial baseline pays it once PER WORKER
    per phase, the parallel fan-out once per phase — which is exactly
    the O(N x RTT) -> O(RTT) claim under test.  ``delay_ms`` must
    DOMINATE the harness noise floor: the in-process cluster runs ~10
    threads per worker on whatever cores CI grants (observed ~100 ms of
    pure scheduler noise for 32 workers on a 2-core box), so a
    too-small delay measures thread scheduling, not fan-out shape.  A
    hung-worker sub-stage (all of one worker's handlers sleeping)
    checks that round start no longer pays ``_call_timeout``
    head-of-line.
    """
    from distpow_tpu.models import puzzle
    from distpow_tpu.nodes import Client, Coordinator, Worker
    from distpow_tpu.runtime import faults, rpc
    from distpow_tpu.runtime.config import (
        ClientConfig,
        CoordinatorConfig,
        WorkerConfig,
    )
    from distpow_tpu.runtime.metrics import REGISTRY
    from distpow_tpu.runtime.telemetry import RECORDER
    from distpow_tpu.runtime.wire import encode_frame, decode_frame

    ntz = 1
    stage_t0 = time.time()

    class _FinderBackend:
        """Control-plane-only miner: the designated finder solves
        instantly, every other worker just honors cancellation.  Real
        python-backend mining would put N GIL-bound search loops in
        this one process and measure interpreter contention, not the
        RPC plane; one-finder-plus-waiters is also the steady-state
        shape of a real round (first-result-wins)."""

        def __init__(self, find: bool):
            self._find = find

        def search(self, nonce, difficulty, thread_bytes, cancel_check=None):
            if self._find:
                return puzzle.python_search(nonce, difficulty, thread_bytes)
            while not (cancel_check and cancel_check()):
                time.sleep(0.002)
            return None

    class _NoCache:
        """Inert worker dominance cache: the stage measures the RPC
        plane, and a real cache lets the reference-parity hit-replay
        race (a waiter's first cache check losing the thread-start
        race against the Found install) mint late results whose full
        Found-rebroadcast rounds pollute the per-round byte windows
        asymmetrically — every stage nonce is fresh, so caching buys
        the measurement nothing."""

        def get(self, nonce, ntz, trace=None):
            return None

        def satisfies(self, nonce, ntz):
            return None

        def add(self, *a, **k):
            pass

        def close(self):
            pass

        def __len__(self):
            return 0
    prev_plan = faults.PLAN
    faults.install_from_spec({"seed": 905, "rules": [
        {"kind": "delay", "side": "server", "method": "WorkerRPCHandler.Mine",
         "delay_s": delay_ms / 1e3},
        {"kind": "delay", "side": "server", "method": "WorkerRPCHandler.Found",
         "delay_s": delay_ms / 1e3},
    ]})

    config_seq = [0]  # distinct nonces per config, deterministically

    def run_config(n, serial, codec, n_rounds, hang_first=False):
        prev_codec = rpc.CLIENT_CODEC_DEFAULT
        rpc.CLIENT_CODEC_DEFAULT = codec
        config_seq[0] += 1
        workers, client, coordinator = [], None, None
        try:
            coordinator = Coordinator(CoordinatorConfig(
                ClientAPIListenAddr="127.0.0.1:0",
                WorkerAPIListenAddr="127.0.0.1:0",
                Workers=["pending:0"] * n,
                FailurePolicy="reassign",
                FailureProbeSecs=1.0,
            ))
            coordinator.handler._serial_fanout = serial
            client_addr, worker_api = coordinator.initialize_rpcs()
            addrs = []
            for i in range(n):
                w = Worker(WorkerConfig(
                    WorkerID=f"cpw{i}", ListenAddr="127.0.0.1:0",
                    CoordAddr=worker_api, Backend="python",
                    WarmupNonceLens=[], WarmupWidths=[],
                ))
                addrs.append(w.initialize_rpcs())
                w.start_forwarder()
                workers.append(w)
            coordinator.set_worker_addrs(addrs)
            finder = 1 if hang_first and n > 1 else 0
            for i, w in enumerate(workers):
                w.handler.backend = _FinderBackend(i == finder)
                w.handler.result_cache = _NoCache()
            if hang_first:
                # one fully frozen worker: every handler sleeps (the
                # in-process stand-in for SIGSTOP; the subprocess
                # variant lives in tests/test_wire.py), with a short
                # ack deadline so each round's bounded cleanup is
                # visible without dominating the stage
                coordinator.handler._call_timeout = 2.0
                hang = lambda params: time.sleep(3600)  # noqa: E731
                workers[0].handler.Mine = hang
                workers[0].handler.Found = hang
                workers[0].handler.Ping = hang
            client = Client(ClientConfig(ClientID="cp", CoordAddr=client_addr))
            client.initialize()
            # one unmeasured warm round: the coordinator dials its N
            # worker connections (and the workers their forwarders)
            # lazily during it, so the one-off JSON rpc.hello handshakes
            # stay OUT of the bytes/round window — they would otherwise
            # count against the binary codec and understate the shrink
            client.mine(bytes([0xC4, config_seq[0], n % 251]), ntz)
            res = client.notify_queue.get(timeout=120)
            assert res.error is None, res.error
            seq0 = (RECORDER.recent(1) or [{"seq": 0}])[-1]["seq"]
            h0 = REGISTRY.get_histogram("rpc.frame.sent_bytes") or \
                {"count": 0, "sum": 0.0}
            lr0 = REGISTRY.get("coord.late_results")
            for i in range(n_rounds):
                nonce = bytes([0xC5, config_seq[0], n % 251, i])
                client.mine(nonce, ntz)
                res = client.notify_queue.get(timeout=120)
                assert res.error is None, res.error
                assert puzzle.check_secret(res.nonce, res.secret, ntz)
            evs = [e for e in RECORDER.recent() if e["seq"] > seq0]
            h1 = REGISTRY.get_histogram("rpc.frame.sent_bytes")
            first = sorted(e["latency_s"] for e in evs
                           if e["kind"] == "coord.first_result")
            cancel = sorted(e["latency_s"] for e in evs
                            if e["kind"] == "coord.cancel_complete")
            return {
                # late non-nil results (the reference-parity cache-hit
                # replay: a waiter whose miner's first cache check lost
                # the thread-scheduling race against the Found install)
                # each cost a FULL Found-rebroadcast round of traffic —
                # window consumers that need clean per-round byte
                # counts (the codec comparison) check this and retry
                "late_results": REGISTRY.get("coord.late_results") - lr0,
                "first_ms": {
                    "p50": round(_cp_percentile(first, 0.5) * 1e3, 3),
                    "p95": round(_cp_percentile(first, 0.95) * 1e3, 3),
                },
                "cancel_ms": {
                    "p50": round(_cp_percentile(cancel, 0.5) * 1e3, 3),
                    "p95": round(_cp_percentile(cancel, 0.95) * 1e3, 3),
                },
                "bytes_per_round": round((h1["sum"] - h0["sum"]) / n_rounds, 1),
                "call_timeout_s": coordinator.handler._call_timeout,
            }
        finally:
            rpc.CLIENT_CODEC_DEFAULT = prev_codec
            if client is not None:
                client.close()
            for w in workers:
                w.shutdown()
            if coordinator is not None:
                coordinator.shutdown()

    out: dict = {"delay_ms": delay_ms, "rounds": rounds, "ntz": ntz,
                 "fanout": {}, "cancel": {}, "speedup": {}}
    try:
        for n in ns:
            row_f, row_c = {}, {}
            # big-N serial rounds cost 2*N*delay each; fewer rounds keep
            # the stage's wall-clock bounded without losing the p95
            n_rounds = max(4, rounds // 2) if n >= 32 else rounds
            for mode, serial in (("serial", True), ("parallel", False)):
                r = run_config(n, serial, "auto", n_rounds)
                row_f[mode] = {"p50_ms": r["first_ms"]["p50"],
                               "p95_ms": r["first_ms"]["p95"]}
                row_c[mode] = {"p50_ms": r["cancel_ms"]["p50"],
                               "p95_ms": r["cancel_ms"]["p95"]}
                print(f"[bench] control-plane n={n} {mode}: "
                      f"first p95 {r['first_ms']['p95']} ms, "
                      f"cancel p95 {r['cancel_ms']['p95']} ms, "
                      f"{r['bytes_per_round']} B/round", file=sys.stderr)
            out["fanout"][f"n{n}"] = row_f
            out["cancel"][f"n{n}"] = row_c
            if row_c["parallel"]["p95_ms"] > 0:
                out["speedup"][f"cancel_p95_n{n}"] = round(
                    row_c["serial"]["p95_ms"] / row_c["parallel"]["p95_ms"], 2)
            if row_f["parallel"]["p95_ms"] > 0:
                out["speedup"][f"first_p95_n{n}"] = round(
                    row_f["serial"]["p95_ms"] / row_f["parallel"]["p95_ms"], 2)

        # json-vs-binary at the production shape (8 workers, parallel).
        # The byte windows must have IDENTICAL round composition on
        # both sides: one cache-hit-replay rebroadcast (late_results —
        # a thread-scheduling race, ~1 in 10 rounds on a loaded 2-core
        # box) landing in only one window skews the ratio by ~8%, which
        # matters against a 2x acceptance floor — retry a polluted
        # window instead of comparing unlike traffic
        def clean_codec_run(codec):
            r = None
            for _attempt in range(3):
                r = run_config(8, False, codec, rounds)
                if not r["late_results"]:
                    return r
                print(f"[bench] control-plane codec window ({codec}) "
                      f"polluted by {r['late_results']} late-result "
                      f"rebroadcast(s); retrying", file=sys.stderr)
            return r

        j = clean_codec_run("json")
        b = clean_codec_run("auto")
        out["codec"] = {
            "json_bytes_per_round": j["bytes_per_round"],
            "binary_bytes_per_round": b["bytes_per_round"],
            "shrink": round(j["bytes_per_round"] /
                            max(b["bytes_per_round"], 1e-9), 2),
            "json_cancel_p95_ms": j["cancel_ms"]["p95"],
            "binary_cancel_p95_ms": b["cancel_ms"]["p95"],
        }
        print(f"[bench] control-plane codec: json {j['bytes_per_round']} "
              f"B/round vs binary {b['bytes_per_round']} B/round "
              f"({out['codec']['shrink']}x shrink)", file=sys.stderr)

        # hung-worker head-of-line check (8 workers, one frozen)
        h = run_config(8, False, "auto", 3, hang_first=True)
        out["hung_worker"] = {
            "call_timeout_s": h["call_timeout_s"],
            "first_p95_ms": h["first_ms"]["p95"],
            "cancel_p95_ms": h["cancel_ms"]["p95"],
        }
        print(f"[bench] control-plane hung worker: first p95 "
              f"{h['first_ms']['p95']} ms (ack deadline "
              f"{h['call_timeout_s']}s off the critical path)",
              file=sys.stderr)

        # codec encode/decode microbenchmark on a representative Mine
        req = {"id": 7, "method": "WorkerRPCHandler.Mine",
               "params": {"nonce": b"\x01\x02\x03\x04",
                          "num_trailing_zeros": 8, "worker_byte": 3,
                          "worker_bits": 3, "round": "00" * 12,
                          "token": bytes(range(40))}}
        import json as _json
        iters = 2000
        t0 = time.perf_counter()
        for _ in range(iters):
            decode_frame(encode_frame(req))
        bin_us = (time.perf_counter() - t0) / iters * 1e6
        t0 = time.perf_counter()
        for _ in range(iters):
            _json.loads(_json.dumps(
                req, default=lambda o: list(o)).encode().decode())
        json_us = (time.perf_counter() - t0) / iters * 1e6
        out["codec_microbench"] = {
            "binary_roundtrip_us": round(bin_us, 2),
            "json_roundtrip_us": round(json_us, 2),
            "binary_bytes": len(encode_frame(req)),
            "json_bytes": len(_json.dumps(req, default=lambda o: list(o))),
        }
    finally:
        faults.install(prev_plan)
    out["wall_s"] = round(time.time() - stage_t0, 1)
    sp = out["speedup"].get("cancel_p95_n8", 0.0)
    if sp < 3.0:
        print(f"[bench] WARNING: cancel p95 speedup at 8 workers is "
              f"{sp}x (< 3x acceptance floor)", file=sys.stderr)
    return out


def load_slo_stage(rates=(6.0, 12.0), duration_s=5.0) -> dict:
    """Open-loop load + cluster SLO stage (``--load-slo``): CPU-only,
    no chip needed (ISSUE 8, ROADMAP open item 5b).

    For each offered arrival rate, replays a seeded Poisson mix with
    Zipf key skew (so the dominance cache and the PR 4 coalescer carry
    their production share of the traffic) against a fresh in-process
    python-backend cluster, while the fleet scraper
    (distpow_tpu/obs/) sweeps the nodes' Stats RPCs and the SLO engine
    judges the merged run window against the checked-in
    ``config/slo.json``.  Reports achieved solves/s and cluster-merged
    Mine p95 per rate; the merged percentile is cross-checked against
    the coordinator's own single-node estimate within one histogram
    bucket (the merge may re-bucket, never relocate — docs/SLO.md).
    """
    from distpow_tpu.load import LoadMix, run_load_slo

    stage_t0 = time.time()
    slo_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "config", "slo.json")
    out: dict = {"slo_config": "config/slo.json",
                 "duration_s": duration_s, "rates": {}, "ok": True}
    for i, rate in enumerate(sorted(rates)):
        mix = LoadMix(
            rate_hz=float(rate), duration_s=float(duration_s),
            seed=41 + i,  # disjoint nonce universes per rate: no
            # cross-rate dominance-cache hits polluting the measurement
            n_keys=16, zipf_s=1.1,
            difficulties=((1, 0.6), (2, 0.4)),
        )
        report, verdict = run_load_slo(
            mix, slo_path, n_workers=2,
            include_worker_targets=True, scrape_interval_s=0.5,
        )
        oracle = report.get("oracle_check") or {}
        row = {
            "target_hz": float(rate),
            "issued": report["load"]["issued"],
            "completed": report["completed"],
            "achieved_solves_per_s": report["achieved_solves_per_s"],
            "client_p95_ms": report["client_latency_ms"]["p95"],
            "merged_miss_p95_ms": report["merged"]["mine_miss_p95_ms"],
            "cache_hits": report["merged"]["cache_hits"],
            "coalesced": report["merged"]["coalesced_requests"],
            "request_errors": report["request_errors"],
            "verdict": verdict.status,
            "oracle_within_bucket": bool(oracle.get("ok")),
            "oracle": oracle,
        }
        out["rates"][f"r{int(rate)}"] = row
        if verdict.exit_code() != 0 or not row["oracle_within_bucket"] \
                or report["request_errors"]:
            out["ok"] = False
        print(f"[bench] load-slo rate {rate}/s: "
              f"{row['achieved_solves_per_s']} solves/s achieved, "
              f"merged miss p95 {row['merged_miss_p95_ms']} ms "
              f"(oracle ok={row['oracle_within_bucket']}), "
              f"verdict {verdict.status}", file=sys.stderr)
    out["wall_s"] = round(time.time() - stage_t0, 1)
    if not out["ok"]:
        print("[bench] WARNING: load-slo stage did not meet its "
              "green-config/oracle acceptance", file=sys.stderr)
    return out


def cluster_scale_stage(pool_sizes=(1, 2, 4), rate_hz=150.0,
                        duration_s=2.0, max_inflight=4,
                        retry_after_s=0.05, solve_delay_s=0.15,
                        drain_timeout_s=60.0) -> dict:
    """Coordinator scale-out stage (``--cluster-scale``): CPU-only,
    no chip needed (ISSUE 15, docs/CLUSTER.md).

    Drives the PR 7 open-loop generator (seeded Poisson arrivals, a
    miss-dominated blend — the key universe is ~4x the request count,
    so coalescing and the dominance cache carry almost nothing) against
    fresh in-process pools of 1, 2 and 4 coordinators sharing one
    worker fleet, and reports aggregate solves/s per pool size.

    What bounds a pool member is its ADMISSION CAPACITY
    (``SchedMaxInflight`` — PR 4's model of one process's bounded run
    queue): each coordinator absorbs ``max_inflight`` concurrent rounds
    and sheds the rest with server-paced RETRY_AFTER, which the
    cluster-aware client rides out (sibling hedge, then the server's
    pacing hint).  Worker solve time is a GIL-releasing stub sleep (the
    control_plane_stage one-finder idiom) sized to DOMINATE scheduler
    noise, so aggregate throughput is ``pool x max_inflight /
    round_time`` by construction and the measured speedup isolates the
    coordinator plane — exactly the "absorb load instead of shedding
    it" claim under test.  Acceptance (asserted into ``ok``): 2
    coordinators >= 1.6x the 1-pool, 4 >= 2.5x (consistent-hash shares
    are not perfectly equal, so the ideal 2x/4x is not the bound).
    """
    from distpow_tpu.load.harness import InProcCluster
    from distpow_tpu.load.loadgen import LoadMix, OpenLoopRunner, \
        build_schedule
    from distpow_tpu.models import puzzle

    stage_t0 = time.time()

    class _DelayFinder:
        """One-finder stub (control_plane_stage idiom): the finder
        sleeps the modeled solve time — releasing the GIL, so
        concurrent rounds genuinely overlap — then solves for real;
        every other worker honors cancellation."""

        def __init__(self, find: bool, delay_s: float):
            self._find = find
            self._delay = delay_s

        def search(self, nonce, difficulty, thread_bytes,
                   cancel_check=None):
            if self._find:
                time.sleep(self._delay)
                return puzzle.python_search(nonce, difficulty,
                                            thread_bytes)
            while not (cancel_check and cancel_check()):
                time.sleep(0.002)
            return None

    def run_pool(n_coordinators: int, seed: int) -> dict:
        import queue as _q
        cluster = InProcCluster(
            n_workers=2, backend="python",
            n_coordinators=n_coordinators,
            coord_extra={
                "SchedMaxInflight": max_inflight,
                "SchedRetryAfterS": retry_after_s,
            },
            # the ceiling must outlast a fully queued backlog's worth
            # of server-paced retries (non-counting for the budget,
            # counting for the ceiling): 50 retries -> 500 attempts
            client_extra={"MineRetries": 50},
        )
        try:
            for j, w in enumerate(cluster.workers):
                w.handler.backend = _DelayFinder(j == 0, solve_delay_s)
            mix = LoadMix(
                rate_hz=rate_hz, duration_s=duration_s, seed=seed,
                n_keys=int(rate_hz * duration_s * 4), zipf_s=0.0,
                difficulties=((1, 1.0),),
            )
            schedule = build_schedule(mix)
            done = [0]
            errors = []
            notify = cluster.client.notify_queue
            stop = [False]

            def drain():
                while not stop[0]:
                    try:
                        res = notify.get(timeout=0.05)
                    except _q.Empty:
                        continue
                    done[0] += 1
                    if res.error:
                        errors.append(str(res.error))

            import threading
            drainer = threading.Thread(target=drain, daemon=True)
            drainer.start()
            t0 = time.monotonic()
            report = OpenLoopRunner(
                lambda arr: cluster.client.mine(arr.nonce, arr.ntz)
            ).run(schedule)
            expected = report.issued - report.submit_errors
            deadline = time.monotonic() + drain_timeout_s
            while done[0] < expected and time.monotonic() < deadline:
                time.sleep(0.02)
            wall = time.monotonic() - t0
            stop[0] = True
            drainer.join(timeout=1.0)
            return {
                "coordinators": n_coordinators,
                "issued": report.issued,
                "completed": done[0],
                "request_errors": len(errors),
                "error_samples": errors[:3],
                "wall_s": round(wall, 3),
                "solves_per_s": round(done[0] / max(wall, 1e-9), 2),
            }
        finally:
            cluster.close()

    out: dict = {
        "rate_hz": rate_hz, "duration_s": duration_s,
        "max_inflight": max_inflight, "solve_delay_s": solve_delay_s,
        "pools": {}, "speedup": {}, "ok": True,
    }
    for i, n in enumerate(sorted(pool_sizes)):
        row = run_pool(n, seed=61 + i)
        out["pools"][f"n{n}"] = row
        if row["request_errors"] or row["completed"] < row["issued"]:
            out["ok"] = False
        print(f"[bench] cluster-scale {n} coordinator(s): "
              f"{row['solves_per_s']} solves/s aggregate "
              f"({row['completed']}/{row['issued']} in "
              f"{row['wall_s']}s, {row['request_errors']} errors)",
              file=sys.stderr)
    base = (out["pools"].get("n1") or {}).get("solves_per_s") or 0.0
    floors = {2: 1.6, 4: 2.5}
    for n in sorted(pool_sizes):
        if n == 1 or not base:
            continue
        x = round((out["pools"][f"n{n}"]["solves_per_s"] or 0.0) / base, 2)
        out["speedup"][f"n{n}_vs_n1"] = x
        floor = floors.get(n)
        if floor is not None and x < floor:
            out["ok"] = False
            print(f"[bench] WARNING: cluster-scale {n}-pool speedup "
                  f"{x}x below the {floor}x acceptance floor",
                  file=sys.stderr)
    out["wall_s"] = round(time.time() - stage_t0, 1)
    return out


def cache_ha_stage(n_keys=12, warm_ntz=2, drain_timeout_s=60.0,
                   converge_timeout_s=20.0) -> dict:
    """Replicated-dominance-cache HA stage (``--cache-ha``): CPU-only,
    no chip needed (ISSUE 16, docs/CLUSTER.md "Replication &
    HA").

    Two arms over identical fresh 2-coordinator in-process pools
    (python-backend workers, localhost RPC), differing ONLY in
    ``ClusterCacheReplicas``: warm a key set split evenly across both
    shards at ``warm_ntz``, wait for write-behind replication to land
    every one of c1's entries on the survivor (peeked via the
    unmetered ``satisfies`` — the replication-off arm has nothing to
    wait for), KILL member c1, then re-mine every key as a dominated
    repeat (ntz=1).  The measurement is the repeat wave's cache-hit
    ratio on the surviving pool:

    * replication ON (``ClusterCacheReplicas=1``, the default):
      every repeat — the dead member's keys included — is served from
      the survivor's replicated dominance cache.  Floors asserted
      into ``ok``: hit ratio 1.0, ZERO fan-out rounds, zero client
      errors;
    * replication OFF (``ClusterCacheReplicas=0``): the dead member's
      keys MISS on the survivor (the ``no_redirect`` failover serve)
      and are RE-MINED — the stage's vs-row is that ratio gap.
      Floors: every dead-owned repeat re-mines (one fan-out round
      each) and the off-arm ratio is exactly the survivor's own
      share.

    Anti-entropy is disabled in both arms (``ClusterAntiEntropyS=0``)
    so the ON arm isolates the write-behind path and the OFF arm
    cannot heal itself.  "Hit" here is the ``coord.mine_s.hit``
    histogram count (the FIRST-lookup warm-serve path), not the raw
    ``cache.hit`` counter — the miss path's final result collection
    re-reads the cache and would double-count every re-mined key.
    Deltas are taken around the repeat wave on the process-global
    REGISTRY — valid because the dead member is already down when the
    wave starts, so only the survivor can tick them.
    """
    import queue as _q

    from distpow_tpu.load.harness import InProcCluster
    from distpow_tpu.runtime.metrics import REGISTRY

    stage_t0 = time.time()

    def run_arm(replicas: int) -> dict:
        cluster = InProcCluster(
            n_workers=2, backend="python", n_coordinators=2,
            coord_extra={
                "ClusterCacheReplicas": replicas,
                "ClusterAntiEntropyS": 0.0,
            },
        )
        try:
            # an even shard split by construction: scan the tag space
            # for the first n/2 keys each member owns, so the off-arm
            # miss count is pinned at exactly n/2 regardless of how
            # the vnode hash happens to carve this pool's ring
            ring = cluster.client.pow._ring
            owned = {"c0": [], "c1": []}
            for i in range(512):
                x = bytes([i & 0xFF, 0x2F ^ (i >> 8)])
                side = ring.owner(x)
                if len(owned[side]) < n_keys // 2:
                    owned[side].append(x)
                if all(len(v) >= n_keys // 2 for v in owned.values()):
                    break
            keys = owned["c0"] + owned["c1"]
            notify = cluster.client.notify_queue

            def mine_wave(ntz: int):
                for x in keys:
                    cluster.client.mine(x, ntz)
                got, errors = [], []
                deadline = time.monotonic() + drain_timeout_s
                while len(got) < len(keys) \
                        and time.monotonic() < deadline:
                    try:
                        res = notify.get(timeout=0.2)
                    except _q.Empty:
                        continue
                    got.append(res)
                    if res.error:
                        errors.append(str(res.error))
                return got, errors

            warm_got, warm_errors = mine_wave(warm_ntz)
            survivor = cluster.coordinators[0].handler.result_cache
            converged = True
            if replicas > 0:
                deadline = time.monotonic() + converge_timeout_s
                while time.monotonic() < deadline:
                    if all(survivor.satisfies(x, warm_ntz) is not None
                           for x in owned["c1"]):
                        break
                    time.sleep(0.05)
                converged = all(
                    survivor.satisfies(x, warm_ntz) is not None
                    for x in owned["c1"])
            def warm_serves() -> int:
                h = REGISTRY.get_histogram("coord.mine_s.hit") or {}
                return int(h.get("count", 0))

            pre_hits = warm_serves()
            pre_fanouts = REGISTRY.get("coord.fanouts")
            cluster.kill_coordinator(1)
            got, errors = mine_wave(1)  # dominated repeats
            d_hits = warm_serves() - pre_hits
            d_fanouts = REGISTRY.get("coord.fanouts") - pre_fanouts
            return {
                "replicas": replicas,
                "keys": len(keys),
                "dead_owned": len(owned["c1"]),
                "warm_completed": len(warm_got),
                "warm_errors": len(warm_errors),
                "converged": converged,
                "repeat_completed": len(got),
                "repeat_errors": len(errors),
                "repeat_hits": d_hits,
                "repeat_fanouts": d_fanouts,
                "repeat_hit_ratio": round(d_hits / max(len(keys), 1),
                                          3),
            }
        finally:
            cluster.close()

    out: dict = {"warm_ntz": warm_ntz, "n_keys": n_keys,
                 "arms": {}, "ok": True}
    for label, replicas in (("repl_on", 1), ("repl_off", 0)):
        arm = run_arm(replicas)
        out["arms"][label] = arm
        print(f"[bench] cache-ha {label}: "
              f"{arm['repeat_hits']}/{arm['keys']} repeat hits "
              f"({arm['repeat_fanouts']} re-mine fan-outs, "
              f"{arm['repeat_errors']} errors, "
              f"converged={arm['converged']})", file=sys.stderr)
    on, off = out["arms"]["repl_on"], out["arms"]["repl_off"]
    out["hit_ratio_on"] = on["repeat_hit_ratio"]
    out["hit_ratio_off"] = off["repeat_hit_ratio"]
    out["on_vs_off_x"] = round(
        on["repeat_hit_ratio"] / max(off["repeat_hit_ratio"], 1e-9), 2)
    # acceptance floors (ISSUE 16): the ON arm rides the kill with a
    # perfect warm-repeat ratio and zero re-mines; the OFF arm pays a
    # re-mine for every key the dead member owned
    if not (on["converged"]
            and on["warm_errors"] == 0 and on["repeat_errors"] == 0
            and on["repeat_hits"] >= on["keys"]
            and on["repeat_fanouts"] == 0):
        out["ok"] = False
        print("[bench] WARNING: cache-ha replication-on arm missed its "
              "floors (want full repeat-hit coverage with zero "
              "fan-outs)", file=sys.stderr)
    if not (off["warm_errors"] == 0 and off["repeat_errors"] == 0
            and off["repeat_hits"] <= off["keys"] - off["dead_owned"]
            and off["repeat_fanouts"] >= off["dead_owned"]):
        out["ok"] = False
        print("[bench] WARNING: cache-ha replication-off arm did not "
              "show the expected miss gap (dead member's keys should "
              "re-mine)", file=sys.stderr)
    out["wall_s"] = round(time.time() - stage_t0, 1)
    return out


def soak_stage(pairs=2, duration_s=8.0, rate_hz=10.0,
               sweep_interval_s=0.25) -> dict:
    """Soak-plane overhead stage (``--soak``): CPU-only, in-process
    cluster, no chip needed (ISSUE 18, docs/SOAK.md).

    The soak plane's cost is its sweep loop: every ``sweep_interval_s``
    the fleet scraper hits the coordinator's Stats RPC (which now also
    samples the resource sentinels) and the merged snapshot lands in
    the retention store.  The acceptance bound is that this observation
    machinery costs under 5% of throughput — measured the only honest
    way, INTERLEAVED off/on arm pairs (off, on, off, on, ...) so drift
    in the host's background load debits both arms equally.  Each arm
    replays the same constant-rate seeded shape through ``run_soak``;
    the off arms push the sweep interval beyond the run length (only
    the gating baseline/final sweeps fire), the on arms sweep at an
    aggressive quarter-second cadence and must also end with a green
    SoakVerdict.
    """
    from distpow_tpu.load import LoadMix, run_soak
    from distpow_tpu.load.shapes import Constant

    stage_t0 = time.time()
    slo_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "config", "slo.json")
    out: dict = {"slo_config": "config/slo.json",
                 "duration_s": duration_s, "rate_hz": rate_hz,
                 "sweep_interval_s": sweep_interval_s,
                 "arms": [], "ok": True}
    on_rates: list = []
    off_rates: list = []
    for p in range(pairs):
        # BOTH arms of a pair replay the SAME seeded schedule —
        # identical arrivals, keys and difficulties — so the only
        # difference between them is the sweep loop being measured
        # (distinct seeds across arms made per-arm schedule variance
        # dwarf the overhead signal); each run boots a FRESH cluster,
        # so the second arm cannot ride the first's dominance cache
        mix = LoadMix(
            rate_hz=1.0, duration_s=1.0,  # placeholders: the shape rules
            seed=1900 + p, n_keys=24, zipf_s=1.1,
            difficulties=((1, 0.7), (2, 0.3)),
        )
        for on in (False, True):  # off first in every pair
            report, verdict = run_soak(
                Constant(rate=float(rate_hz),
                         duration_s=float(duration_s)),
                mix, slo_path, n_workers=2,
                # an interval past any plausible run length disables
                # the periodic loop; baseline/final sweeps still gate
                scrape_interval_s=(sweep_interval_s if on else 1e9),
            )
            row = {
                "arm": "on" if on else "off",
                "pair": p,
                "achieved_solves_per_s": report["achieved_solves_per_s"],
                "completed": report["completed"],
                "request_errors": report["request_errors"],
                "retained_points": report["retention"]["points"],
                "verdict": verdict.status,
            }
            out["arms"].append(row)
            (on_rates if on else off_rates).append(
                row["achieved_solves_per_s"])
            if report["request_errors"] \
                    or (on and verdict.exit_code() != 0):
                out["ok"] = False
            print(f"[bench] soak pair {p} ({row['arm']}): "
                  f"{row['achieved_solves_per_s']} solves/s, "
                  f"{row['retained_points']} retained point(s), "
                  f"verdict {verdict.status}", file=sys.stderr)
    mean_on = sum(on_rates) / max(len(on_rates), 1)
    mean_off = sum(off_rates) / max(len(off_rates), 1)
    overhead = (max(0.0, (1.0 - mean_on / mean_off) * 100.0)
                if mean_off > 0 else 0.0)
    out["on_solves_per_s"] = round(mean_on, 3)
    out["off_solves_per_s"] = round(mean_off, 3)
    out["overhead_pct"] = round(overhead, 2)
    out["overhead_ok"] = overhead < 5.0
    if not out["overhead_ok"]:
        out["ok"] = False
        print(f"[bench] WARNING: soak sweep overhead "
              f"{out['overhead_pct']}% exceeds the 5% bound",
              file=sys.stderr)
    out["wall_s"] = round(time.time() - stage_t0, 1)
    return out


def membership_stage(straggler_cap_s=8.0, solve_delay_s=1.0) -> dict:
    """Elastic-membership latency stage (``--membership``): CPU-only,
    in-process cluster, no chip needed (ISSUE 12).

    Two sub-stages, both built from lease-registered python-backend
    workers whose miner is a deterministic designated-finder stub (only
    the shard holding first-byte 0 can solve, after ``solve_delay_s`` —
    so round completion time is governed by WHO holds that shard and
    how fast the control plane moves it, not by hash throughput):

    * **reassignment**: the finder-shard owner goes fully silent
      (every handler wedged, heartbeats stopped — the in-process
      stand-in for SIGKILL-with-open-TCP).  Measured round completion
      under lease expiry (short TTL retires the lease, which closes the
      connection and drops the shard into orphan reassignment) vs the
      PR 5 probe baseline (static workers, same freeze: detection waits
      for the liveness probe's 2 s ping timeout).
    * **straggler**: the owner's RPC surface stays perfectly healthy —
      Ping answers, Found acks — but its miner is stuck and its
      heartbeats stop: the exact failure probes CANNOT see.  Measured:
      all-healthy round, hedged round (one frozen of four; must land
      within 2x healthy — the ISSUE 12 acceptance), and the hedging-off
      floor, which never completes and is reported as the measurement
      cap (the unbounded wait-for-straggler this stage exists to kill).
    """
    from distpow_tpu.models import puzzle
    from distpow_tpu.nodes import Client, Coordinator, Worker
    from distpow_tpu.runtime.config import (
        ClientConfig,
        CoordinatorConfig,
        WorkerConfig,
    )
    from distpow_tpu.runtime.metrics import REGISTRY

    stage_t0 = time.time()
    ntz = 1

    class _FinderStub:
        """Solves only when its shard holds first-byte 0 (after a fixed
        delay); honors cancellation otherwise.  ``frozen`` wedges the
        miner (not the RPC surface) until released."""

        def __init__(self):
            self.frozen = False

        def search(self, nonce, difficulty, thread_bytes, cancel_check=None):
            while self.frozen and not (cancel_check and cancel_check()):
                time.sleep(0.02)
            if 0 in thread_bytes:
                deadline = time.monotonic() + solve_delay_s
                while time.monotonic() < deadline:
                    if cancel_check and cancel_check():
                        return None
                    time.sleep(0.01)
                return puzzle.python_search(nonce, difficulty, thread_bytes)
            while not (cancel_check and cancel_check()):
                time.sleep(0.02)
            return None

    def boot(n, elastic, coord_extra=None, heartbeat_s=0.15):
        coordinator = Coordinator(CoordinatorConfig(
            ClientAPIListenAddr="127.0.0.1:0",
            WorkerAPIListenAddr="127.0.0.1:0",
            Workers=[] if elastic else ["pending:0"] * n,
            FailurePolicy="reassign",
            FailureProbeSecs=0.25,
            **(coord_extra or {}),
        ))
        client_addr, worker_api = coordinator.initialize_rpcs()
        workers, addrs = [], []
        for i in range(n):
            w = Worker(WorkerConfig(
                WorkerID=f"mw{i}", ListenAddr="127.0.0.1:0",
                CoordAddr=worker_api, Backend="python",
                WarmupNonceLens=[], WarmupWidths=[],
                FleetRegister=elastic, FleetHeartbeatS=heartbeat_s,
                FleetCalibrationS=0.0, FleetMHS=1.0,
            ))
            addrs.append(w.initialize_rpcs())
            w.start_forwarder()
            w.handler.backend = _FinderStub()
            if elastic:
                w.start_fleet_agent()
                assert w.fleet_agent.wait_registered(10.0)
            workers.append(w)
        if not elastic:
            coordinator.set_worker_addrs(addrs)
        client = Client(ClientConfig(ClientID="mb", CoordAddr=client_addr))
        client.initialize()
        return coordinator, workers, client

    def teardown(coordinator, workers, client):
        client.close()
        for w in workers:
            if w.fleet_agent is not None:
                # skip the graceful drain: sub-stages leave wedged
                # members behind by design, and teardown must not wait
                # out their drain timeouts
                w.fleet_agent.stop(drain=False)
                w.fleet_agent = None
            w.shutdown()
        coordinator.shutdown()

    def timed_round(client, nonce, timeout=60.0):
        t0 = time.monotonic()
        client.mine(nonce, ntz)
        res = client.notify_queue.get(timeout=timeout)
        assert res.error is None, res.error
        assert puzzle.check_secret(res.nonce, res.secret, ntz)
        return time.monotonic() - t0

    def freeze_silent(w):
        """Full silence: every RPC handler wedges, heartbeats stop."""
        if w.fleet_agent is not None:
            w.fleet_agent.pause()
        hang = lambda params: time.sleep(3600)  # noqa: E731
        w.handler.Mine = hang
        w.handler.Found = hang
        w.handler.Ping = hang

    out: dict = {"solve_delay_s": solve_delay_s, "ntz": ntz}

    # -- sub-stage A: reassignment latency on silent worker death ------
    rows = {}
    for mode, elastic, extra in (
        ("lease_expiry", True, {"FleetLeaseTTLS": 0.6, "FleetHedge": False}),
        ("probe_baseline", False, {}),
    ):
        # n=4: a DISJOINT reference split (non-power-of-two counts wrap
        # worker n-1 back onto shard 0, which would hand the frozen
        # owner's bytes to a healthy twin and void the measurement)
        coordinator, workers, client = boot(4, elastic, coord_extra=extra)
        try:
            healthy = timed_round(client, bytes([0xD0, 1 if elastic else 2]))
            # the finder-shard owner is the FIRST member (shard 0 holds
            # byte 0 in the n=4 reference split); silence it and time
            # the recovery round end to end
            freeze_silent(workers[0])
            dead = timed_round(
                client, bytes([0xD1, 1 if elastic else 2]), timeout=120.0)
            rows[mode] = {"healthy_s": round(healthy, 3),
                          "dead_worker_s": round(dead, 3),
                          "detection_overhead_s": round(
                              max(0.0, dead - healthy), 3)}
            print(f"[bench] membership reassignment [{mode}]: healthy "
                  f"{healthy:.2f}s, silent-owner round {dead:.2f}s",
                  file=sys.stderr)
        finally:
            teardown(coordinator, workers, client)
    if rows.get("probe_baseline", {}).get("detection_overhead_s", 0) > 0:
        rows["lease_vs_probe_x"] = round(
            rows["probe_baseline"]["detection_overhead_s"]
            / max(rows["lease_expiry"]["detection_overhead_s"], 1e-3), 2)
    out["reassignment"] = rows

    # -- sub-stage B: straggler, hedging on vs off ----------------------
    st: dict = {"n_workers": 4, "cap_s": straggler_cap_s}
    for mode, hedge in (("hedged", True), ("hedge_off", False)):
        coordinator, workers, client = boot(
            4, True, heartbeat_s=0.1,
            coord_extra={"FleetLeaseTTLS": 60.0, "FleetHedge": hedge,
                         "FleetHedgeMultiple": 2.0},
        )
        try:
            healthy = timed_round(client, bytes([0xD2, hedge]))
            st.setdefault("healthy_s", round(healthy, 3))
            # straggler: miner wedged + beats stopped, RPC surface alive
            workers[0].handler.backend.frozen = True
            workers[0].fleet_agent.pause()
            time.sleep(0.3)  # let the silence exceed the hedge threshold
            t0 = time.monotonic()
            client.mine(bytes([0xD3, hedge]), ntz)
            try:
                res = client.notify_queue.get(timeout=straggler_cap_s)
            except queue.Empty:
                # ONLY a timeout is the floor: the unbounded
                # wait-for-straggler outcome, reported as >= cap.  An
                # error-completed round must surface as the stage
                # failure it is, not masquerade as the floor while the
                # cleanup waits a minute for a reply already consumed.
                st[f"{mode}_s"] = None
                st[f"{mode}_floor_s"] = straggler_cap_s
                # release the wedge so the round drains and teardown
                # does not fight a stuck miner
                workers[0].handler.backend.frozen = False
                workers[0].fleet_agent.resume()
                client.notify_queue.get(timeout=60.0)
            else:
                wall = time.monotonic() - t0
                assert res.error is None, res.error
                st[f"{mode}_s"] = round(wall, 3)
            hs = st.get(f"{mode}_s")
            print(f"[bench] membership straggler [{mode}]: "
                  f"{'>= %.1fs (capped)' % straggler_cap_s if hs is None else '%.2fs' % hs}"
                  f" (healthy {st['healthy_s']}s, "
                  f"hedged_shards={REGISTRY.get('fleet.hedged_shards')})",
                  file=sys.stderr)
        finally:
            teardown(coordinator, workers, client)
    if st.get("hedged_s") and st.get("healthy_s"):
        st["hedged_vs_healthy_x"] = round(
            st["hedged_s"] / st["healthy_s"], 2)
    out["straggler"] = st
    out["wall_s"] = round(time.time() - stage_t0, 1)
    ok = (st.get("hedged_s") is not None and st.get("healthy_s")
          and st["hedged_s"] <= 2.0 * st["healthy_s"])
    out["hedge_within_2x_healthy"] = bool(ok)
    if not ok:
        print("[bench] WARNING: hedged straggler round exceeded the 2x "
              "all-healthy acceptance bound", file=sys.stderr)
    return out


def forensics_overhead_stage(rounds_per_arm=30, ntz=1) -> dict:
    """Forensics-overhead stage (``--forensics-overhead``): CPU-only,
    no chip needed (ISSUE 14).

    Measures what the request-forensics plane COSTS on the serving
    path: end-to-end Mine rounds through a real in-process cluster
    (coordinator + 2 python-backend workers over localhost RPC, fresh
    nonce per round so every solve is real work) with spans + histogram
    exemplars ON vs OFF.  The two arms run INTERLEAVED (on, off, on,
    off, ...) and compare medians, so machine-load drift hits both
    equally instead of masquerading as overhead.

    Acceptance (asserted here): spans+exemplars-on serving throughput
    within 5% of off — with a 1 ms absolute slack on the median round
    so 2-core scheduler noise on a ~10 ms baseline cannot flake a bound
    the real overhead (tens of µs of dict+deque appends per round)
    never approaches.
    """
    from distpow_tpu.models import puzzle
    from distpow_tpu.nodes import Client, Coordinator, Worker
    from distpow_tpu.runtime.config import (
        ClientConfig,
        CoordinatorConfig,
        WorkerConfig,
    )
    from distpow_tpu.runtime.metrics import REGISTRY
    from distpow_tpu.runtime.spans import SPANS

    stage_t0 = time.time()
    coordinator = Coordinator(CoordinatorConfig(
        ClientAPIListenAddr="127.0.0.1:0",
        WorkerAPIListenAddr="127.0.0.1:0",
        Workers=["pending:0"] * 2,
    ))
    client_addr, worker_api = coordinator.initialize_rpcs()
    workers, addrs = [], []
    for i in range(2):
        w = Worker(WorkerConfig(
            WorkerID=f"fo{i}", ListenAddr="127.0.0.1:0",
            CoordAddr=worker_api, Backend="python",
            WarmupNonceLens=[], WarmupWidths=[],
        ))
        addrs.append(w.initialize_rpcs())
        w.start_forwarder()
        workers.append(w)
    coordinator.set_worker_addrs(addrs)
    client = Client(ClientConfig(ClientID="fo", CoordAddr=client_addr))
    client.initialize()

    # seq delta, NOT ring length: earlier same-process stages (load-slo,
    # membership) may have saturated the bounded ring, whose length then
    # never moves again (review PR 9)
    spans_before = SPANS.total_recorded
    # restore the operator's ACTUAL prior state afterwards — a
    # DISTPOW_SPANS=0 run must stay disabled for the rest of the bench
    prev_spans = SPANS.enabled
    prev_exemplars = REGISTRY.exemplars_enabled
    durs = {"on": [], "off": []}
    try:
        # warmup rounds: first-dial lazy connects and allocator noise
        # must not land inside either arm
        for i in range(4):
            client.mine(bytes([0xF0, i]), ntz)
            assert client.notify_queue.get(timeout=60).error is None
        seq = 0
        for _ in range(rounds_per_arm):
            for arm in ("on", "off"):
                on = arm == "on"
                SPANS.configure(enabled=on)
                REGISTRY.exemplars_enabled = on
                seq += 1
                nonce = bytes([0xF1, seq & 0xFF, seq >> 8])
                t0 = time.monotonic()
                client.mine(nonce, ntz)
                res = client.notify_queue.get(timeout=60)
                durs[arm].append(time.monotonic() - t0)
                assert res.error is None, res.error
                assert puzzle.check_secret(res.nonce, res.secret, ntz)
    finally:
        SPANS.configure(enabled=prev_spans)
        REGISTRY.exemplars_enabled = prev_exemplars
        client.close()
        for w in workers:
            w.shutdown()
        coordinator.shutdown()

    def median(vals):
        s = sorted(vals)
        return s[len(s) // 2]

    med_on, med_off = median(durs["on"]), median(durs["off"])
    ratio = (1.0 / med_on) / (1.0 / med_off)  # on-vs-off throughput
    spans_on = SPANS.total_recorded - spans_before
    exemplar_hist = REGISTRY.get_histogram("coord.mine_s.miss") or {}
    out = {
        "rounds_per_arm": rounds_per_arm,
        "ntz": ntz,
        "on": {"median_round_s": round(med_on, 6),
               "solves_per_s": round(1.0 / med_on, 3)},
        "off": {"median_round_s": round(med_off, 6),
                "solves_per_s": round(1.0 / med_off, 3)},
        "on_vs_off_x": round(ratio, 4),
        "overhead_pct": round((med_on / med_off - 1.0) * 100.0, 2),
        "spans_recorded_on_arm": spans_on,
        "exemplars_present": bool(exemplar_hist.get("exemplars")),
        "wall_s": round(time.time() - stage_t0, 1),
    }
    ok = med_on <= med_off * 1.05 + 0.001
    out["within_5pct"] = bool(ok)
    print(f"[bench] forensics overhead: on {out['on']['solves_per_s']} "
          f"vs off {out['off']['solves_per_s']} solves/s "
          f"({out['overhead_pct']}% overhead, {spans_on} spans captured)",
          file=sys.stderr)
    # the on-arm must actually have exercised the plane, or the
    # comparison proves nothing
    assert spans_on > 0, "spans-on arm recorded no spans"
    assert ok, (
        f"forensics overhead outside the 5% acceptance bound: median "
        f"round {med_on * 1e3:.2f}ms on vs {med_off * 1e3:.2f}ms off"
    )
    return out


def serving_stage(ks=(1, 4, 16)) -> dict:
    """Aggregate serving throughput under concurrency (``--serving``).

    Measures end-to-end solves/s through a REAL in-process stack —
    coordinator + one worker with the continuous-batching scheduler
    (docs/SCHEDULER.md) — at K concurrent same-difficulty Mine
    requests.  The K=1 column is the one-launch-per-request baseline;
    the batching win is the K=4/K=16 aggregate staying a multiple of
    it instead of flat.  Fresh nonces per request (no cache hits), so
    every solve is real device work.  Prints ONE JSON line and returns
    it; deliberately OUTSIDE the provenance/anomaly machinery — this
    is a serving-plane number, not a kernel rate.
    """
    from distpow_tpu.models import puzzle
    from distpow_tpu.nodes import Client, Coordinator, Worker
    from distpow_tpu.runtime.config import (
        ClientConfig,
        CoordinatorConfig,
        WorkerConfig,
    )
    from distpow_tpu.runtime.metrics import REGISTRY

    ntz = int(os.environ.get("BENCH_SERVING_NTZ", "4"))
    batch = int(os.environ.get("BENCH_SERVING_BATCH", str(1 << 14)))
    coordinator = Coordinator(CoordinatorConfig(
        ClientAPIListenAddr="127.0.0.1:0",
        WorkerAPIListenAddr="127.0.0.1:0",
        Workers=["pending:0"],
    ))
    client_addr, worker_api_addr = coordinator.initialize_rpcs()
    worker = Worker(WorkerConfig(
        WorkerID="bench-worker",
        ListenAddr="127.0.0.1:0",
        CoordAddr=worker_api_addr,
        Backend="jax",
        Scheduler="batching",
        SchedMaxSlots=max(ks),
        BatchSize=batch,
        WarmupNonceLens=[],
        WarmupWidths=[],
    ))
    coordinator.set_worker_addrs([worker.initialize_rpcs()])
    worker.start_forwarder()
    client = Client(ClientConfig(ClientID="bench", CoordAddr=client_addr))
    client.initialize()
    stages: dict = {}
    try:
        # one throwaway solve pays the compile before any timed column
        client.mine(b"\xb0\xff", ntz)
        assert client.notify_queue.get(timeout=600).error is None
        for k in ks:
            occ0 = REGISTRY.get_histogram("sched.batch_occupancy") or \
                {"count": 0, "sum": 0.0}
            nonces = [bytes([0xB0, k, i]) for i in range(k)]
            t0 = time.monotonic()
            for n in nonces:
                client.mine(n, ntz)
            for _ in range(k):
                res = client.notify_queue.get(timeout=600)
                assert res.error is None, res.error
                assert puzzle.check_secret(res.nonce, res.secret, ntz)
            dt = time.monotonic() - t0
            occ1 = REGISTRY.get_histogram("sched.batch_occupancy")
            n_launch = occ1["count"] - occ0["count"]
            stages[f"k{k}"] = {
                "solves_per_s": round(k / dt, 3),
                "wall_s": round(dt, 3),
                "launches": n_launch,
                "mean_occupancy": round(
                    (occ1["sum"] - occ0["sum"]) / max(n_launch, 1), 3),
            }
            print(f"[bench] serving k={k}: "
                  f"{stages[f'k{k}']['solves_per_s']} solves/s "
                  f"(occupancy {stages[f'k{k}']['mean_occupancy']})",
                  file=sys.stderr)
    finally:
        client.close()
        worker.shutdown()
        coordinator.shutdown()
    line = {
        "metric": f"serving solves/s, continuous batching, ntz={ntz}",
        "unit": "solves/s",
        "value": stages[f"k{max(ks)}"]["solves_per_s"],
        "stages": stages,
    }
    print(json.dumps(line))
    return line


def mesh_serving_arm(n_requested: int) -> dict:
    """One ``--mesh-serving-arm`` child: scheduler solves/s at this
    process's virtual-CPU-device count.

    The device count is fixed at backend initialization, so each arm
    needs its own process — the parent (``mesh_serving_stage``) spawns
    this entry point with the count pre-set via
    ``compat.cpu_devices_env``.  The arm solves an identical seeded
    nonce set through a stock ``BatchingScheduler`` (lane override left
    at ``auto``, so the lane planner picks mesh at 4 devices and xla at
    1 — the comparison is the planner's own choice at each width, not a
    forced lane).  One warm solve pays every compile outside the timed
    window; the per-lane launch counters ride home so the parent can
    assert the mesh lane actually served.
    """
    import jax
    import jax.numpy as jnp

    from distpow_tpu.models import puzzle
    from distpow_tpu.ops.difficulty import nibble_masks
    from distpow_tpu.ops.packing import build_tail_spec
    from distpow_tpu.ops.search_step import slot_search_step
    from distpow_tpu.runtime.metrics import REGISTRY
    from distpow_tpu.sched.engine import BatchingScheduler

    devices = len(jax.devices())
    ntz = int(os.environ.get("BENCH_MESH_SERVING_NTZ", "4"))
    solves = int(os.environ.get("BENCH_MESH_SERVING_SOLVES", "24"))
    # serving-shaped batch: small enough that per-launch host overhead
    # is a real fraction of each solve (the regime the mesh lane's
    # span amortization targets), large enough that the 1-device arm
    # is not purely python-bound
    batch = int(os.environ.get("BENCH_MESH_SERVING_BATCH", "1024"))
    lane_keys = [f"sched.lane_launches.{l}" for l in
                 ("pallas", "mesh", "xla")]
    eng = BatchingScheduler(hash_model="md5", batch_size=batch,
                            max_slots=4)
    try:
        # warm solve: compiles (and the mesh lane's operand placement)
        # happen outside the timed window; same nonce SHAPE as the
        # timed set so the timed solves hit the same cached programs
        warm_nonce = bytes([0xE0, 0xFF, 0x3C])
        warm = eng.search(warm_nonce, ntz, list(range(256)))
        assert warm is not None and puzzle.check_secret(warm_nonce, warm,
                                                        ntz)
        # warm every WIDTH layout the timed solves can touch: a solve
        # that exhausts its width-1 segment advances to the width-2
        # tail layout, which is a fresh compile key — a production
        # server compiles each layout once per lifetime, so the timed
        # window must not pay it either (on the planner-picked lane,
        # whichever that is at this device count)
        for vw in (1, 2):
            spec = build_tail_spec(warm_nonce, vw, eng.model, b"")
            gdef = ("md5", spec.n_blocks, spec.tb_loc, spec.chunk_locs, 1)
            _, gstep = eng.planner.resolve(gdef, batch)
            ops = (
                jnp.stack([jnp.asarray(spec.init_state, jnp.uint32)]),
                jnp.stack([jnp.asarray(spec.base_words, jnp.uint32)]),
                jnp.stack([jnp.asarray(nibble_masks(ntz, eng.model),
                                       jnp.uint32)]),
                jnp.zeros(1, jnp.uint32),
                jnp.full(1, 8, jnp.uint32),
                jnp.asarray([256 ** (vw - 1)], jnp.uint32),
            )
            if gstep is not None:
                jax.device_get(gstep(ops, ("warm", vw)))
            else:
                xla_step = slot_search_step(
                    "md5", spec.n_blocks, spec.tb_loc, spec.chunk_locs,
                    batch, 1,
                )
                jax.device_get(xla_step(*ops))
        before = {k: REGISTRY.get(k) for k in lane_keys}
        t0 = time.monotonic()
        for i in range(solves):
            nonce = bytes([0xE0, i, 0x3C])
            secret = eng.search(nonce, ntz, list(range(256)))
            assert secret is not None and puzzle.check_secret(nonce,
                                                              secret, ntz)
        wall = time.monotonic() - t0
        lanes = {k.rsplit(".", 1)[-1]: REGISTRY.get(k) - before[k]
                 for k in lane_keys}
    finally:
        eng.close()
    return {
        "devices": devices,
        "requested_devices": n_requested,
        "ntz": ntz,
        "batch": batch,
        "solves": solves,
        "wall_s": round(wall, 3),
        "solves_per_s": round(solves / max(wall, 1e-9), 3),
        "lane_launches": {l: v for l, v in lanes.items() if v},
    }


def mesh_serving_stage(timeout_s: float = 600.0):
    """Mesh-serving scale stage (``--mesh-serving``): CPU-only, no
    chip needed (ISSUE 20).

    Spawns one CPU-pinned subprocess per arm — 1 and 4 virtual CPU
    devices via the pre-init XLA host-device-count flag
    (``compat.cpu_devices_env``; the count cannot be changed once a
    backend initializes, hence subprocesses) — and compares scheduler
    solves/s over the identical seeded solve set.  Both arms enumerate
    the same candidate order, so the per-solve work is deterministic
    and equal; the 4-device arm wins purely by covering n_dev x batch
    candidates per launch (docs/SERVING.md).  Acceptance: >= 2x
    solves/s at 4 devices, with the mesh lane actually serving
    (``sched.lane_launches.mesh`` > 0) — both asserted into ``ok``.

    The arms are CPU-pinned subprocesses, so they never load the TPU
    runtime; child provenance is redirected to a temp path as a belt-and-braces
    guard even though the arm entry point never writes provenance.
    """
    import subprocess
    import tempfile

    from distpow_tpu.parallel import compat

    arms = {}
    for n in (1, 4):
        env = compat.cpu_devices_env(n)
        env["BENCH_FORCE_PLATFORM"] = "cpu"
        try:
            with tempfile.TemporaryDirectory() as td:
                env["BENCH_LAST_MEASURED_PATH"] = os.path.join(td,
                                                               "lm.json")
                out = subprocess.run(
                    [sys.executable, os.path.abspath(__file__),
                     "--mesh-serving-arm", str(n)],
                    capture_output=True, text=True, timeout=timeout_s,
                    env=env,
                )
        except subprocess.TimeoutExpired:
            print(f"[bench] mesh-serving arm {n} exceeded {timeout_s}s "
                  f"in its CPU subprocess", file=sys.stderr)
            return None
        if out.stderr:
            sys.stderr.write(out.stderr)
        try:
            arms[n] = json.loads(out.stdout.strip().splitlines()[-1])
        except Exception as exc:
            print(f"[bench] mesh-serving arm {n} failed "
                  f"(rc={out.returncode}): {exc}", file=sys.stderr)
            return None
    sps1 = arms[1]["solves_per_s"]
    sps4 = arms[4]["solves_per_s"]
    speedup = round(sps4 / max(sps1, 1e-9), 2)
    mesh_launches = arms[4]["lane_launches"].get("mesh", 0)
    ok = (speedup >= 2.0 and mesh_launches > 0
          and arms[4]["devices"] == 4)
    out = {
        "ntz": arms[1]["ntz"],
        "batch": arms[1]["batch"],
        "solves": arms[1]["solves"],
        "arms": [arms[1], arms[4]],
        "speedup_x": speedup,
        "ok": ok,
    }
    print(f"[bench] mesh-serving: {sps4} solves/s at 4 devices vs "
          f"{sps1} at 1 ({speedup}x, mesh launches {mesh_launches})",
          file=sys.stderr)
    if not ok:
        print(f"[bench] WARNING: mesh-serving stage failed its floors "
              f"(speedup {speedup}x < 2x, mesh launches "
              f"{mesh_launches}, or 4-device arm booted "
              f"{arms[4]['devices']} devices)", file=sys.stderr)
    return out


def main() -> None:
    forced = os.environ.get("BENCH_FORCE_PLATFORM")
    if forced:
        import jax

        jax.config.update("jax_platforms", forced)
    if "--serving" in sys.argv:
        serving_stage()
        return
    if "--mesh-serving-arm" in sys.argv:
        # one CPU-pinned child of the --mesh-serving stage: the
        # virtual-device count is fixed at backend init, so each arm is
        # its own process.  Request the count here too (pre-init env
        # flag on versions without the config option) so a hand-run
        # arm works without the parent's environment; prints the arm
        # dict as its only stdout line — no finalize_record, no
        # provenance.
        from distpow_tpu.parallel import compat

        if not forced:
            import jax

            jax.config.update("jax_platforms", "cpu")
        n = int(sys.argv[sys.argv.index("--mesh-serving-arm") + 1])
        compat.request_cpu_devices(n)
        print(json.dumps(mesh_serving_arm(n)))
        return
    if "--mesh-serving" in sys.argv:
        # standalone mesh-serving scale run (ISSUE 20): CPU-only by
        # construction — each arm is a CPU-pinned subprocess with a
        # fixed virtual-device count; the >=2x speedup / mesh-lane-served floors are
        # asserted into the stage's ok and the line rides
        # finalize_record's mesh-serving shape (kernel provenance
        # untouched)
        ms = mesh_serving_stage()
        if ms is None:
            sys.exit(1)
        line, _ = finalize_record({}, _read_last_measured(), None,
                                  mesh_serving=ms)
        print(json.dumps(line))
        return
    if "--control-plane" in sys.argv:
        # standalone control-plane run: CPU-only, no device probe, the
        # line rides finalize_record's control-plane shape and kernel
        # provenance stays untouched (docstring there)
        cp = control_plane_stage()
        line, _ = finalize_record({}, _read_last_measured(), None,
                                  control_plane=cp)
        print(json.dumps(line))
        return
    if "--load-slo" in sys.argv:
        # standalone open-loop load + SLO run (ISSUE 8): CPU-only by
        # construction — python-backend workers, localhost RPC, no jax;
        # the line rides finalize_record's load-slo shape and kernel
        # provenance stays untouched (docstring there)
        ls = load_slo_stage()
        line, _ = finalize_record({}, _read_last_measured(), None,
                                  load_slo=ls)
        print(json.dumps(line))
        return
    if "--membership" in sys.argv:
        # standalone elastic-membership run (ISSUE 12): CPU-only by
        # construction — python-backend workers with stub miners over
        # localhost RPC, no jax and no device probe; the line rides
        # finalize_record's membership shape and kernel provenance
        # stays untouched (docstring there)
        mb = membership_stage()
        line, _ = finalize_record({}, _read_last_measured(), None,
                                  membership=mb)
        print(json.dumps(line))
        return
    if "--cluster-scale" in sys.argv:
        # standalone coordinator-pool scaling run (ISSUE 15): CPU-only
        # by construction — stub-backend workers over localhost RPC,
        # no jax and no device probe; the 1.6x/2.5x acceptance floors
        # are asserted inside the stage and the line rides
        # finalize_record's cluster-scale shape (kernel provenance
        # untouched)
        cs = cluster_scale_stage()
        line, _ = finalize_record({}, _read_last_measured(), None,
                                  cluster_scale=cs)
        print(json.dumps(line))
        return
    if "--cache-ha" in sys.argv:
        # standalone cache-HA run (ISSUE 16): CPU-only by construction
        # — python-backend workers over in-process RPC, no jax and no
        # device probe; the 1.0-hit-ratio / zero-fanout floors are
        # asserted inside the stage and the line rides
        # finalize_record's cache-ha shape (kernel provenance
        # untouched)
        ch = cache_ha_stage()
        line, _ = finalize_record({}, _read_last_measured(), None,
                                  cache_ha=ch)
        print(json.dumps(line))
        return
    if "--soak" in sys.argv:
        # standalone soak-overhead run (ISSUE 18): CPU-only by
        # construction — python-backend workers over localhost RPC, no
        # jax and no device probe; the <5% sweep-overhead bound and the
        # on-arm green verdicts are asserted inside the stage and the
        # line rides finalize_record's soak shape (kernel provenance
        # untouched)
        sk = soak_stage()
        line, _ = finalize_record({}, _read_last_measured(), None,
                                  soak=sk)
        print(json.dumps(line))
        return
    if "--forensics-overhead" in sys.argv:
        # standalone forensics-overhead run (ISSUE 14): CPU-only by
        # construction — python-backend workers over localhost RPC, no
        # jax and no device probe; the 5% acceptance bound is asserted
        # inside the stage and the line rides finalize_record's
        # forensics shape (kernel provenance untouched)
        fo = forensics_overhead_stage()
        line, _ = finalize_record({}, _read_last_measured(), None,
                                  forensics=fo)
        print(json.dumps(line))
        return
    # the device bench measures the chip: JAX is pinned to the TPU, so
    # a host without one fails here instead of measuring the CPU
    # (BENCH_FORCE_PLATFORM=cpu is the CPU rehearsal, scripts/ci.sh).
    # The persistent compile cache is on before the first compile
    # (runtime/compile_cache.py: $JAX_COMPILATION_CACHE_DIR, else
    # <checkout>/.jax_cache).
    from distpow_tpu.runtime.compile_cache import enable as _enable_cache

    _enable_cache()
    import jax

    if not forced:
        jax.config.update("jax_platforms", "tpu")
    try:
        devices = jax.devices()
    except RuntimeError as exc:
        print(f"[bench] no TPU: {exc}", file=sys.stderr)
        sys.exit(2)
    print(f"[bench] devices: {devices}", file=sys.stderr)

    last_measured = _read_last_measured()
    # Optional-stage deadline (seconds of total bench wall-clock): the
    # mandatory phases A-C always run; the e2e solves and diagnostic
    # serving lines are skipped once the run exceeds this.
    deadline = time.time() + float(os.environ.get("BENCH_DEADLINE_S", "600"))

    # A dispatch that never returns leaves the process hung with no
    # JSON line.  The device-hang watchdog's on_hang prints what was
    # measured before the hang and exits NON-ZERO.  420s >> the longest
    # legitimate beat gap between launches; single first-compiles get a
    # wider window via WATCHDOG.grace in device_rate (sha512's compile
    # has out-waited 420 s on a healthy device).
    rates: dict = {}  # filled stage by stage; the hang bailout reads it
    state: dict = {"baseline": None}  # phase-C native baseline, in H/s

    def _hang_bailout(stale: float) -> None:
        # Salvage everything measured BEFORE the hang: the md5 headline
        # stages run first precisely so a late-stage death (a diagnostic
        # model's compile or the e2e tail) cannot zero the round's
        # number (r4 first attempt: 0.0 despite md5 at 10 GH/s in the
        # same log).  Snapshot first — this runs on the monitor thread
        # while the main thread may still be inserting (review r4: a
        # mid-iteration insert would RuntimeError the monitor and
        # silently disarm hang protection).
        snap = dict(rates)
        lm = _read_last_measured()
        if any(l in MD5_LABELS for l in snap):
            # baseline: prefer the one measured THIS run (phase C runs
            # early now); finalize_record falls back to deriving it
            # from the provenance file otherwise — never run new work
            # from inside the monitor thread
            line, prov = finalize_record(
                snap, lm, state["baseline"],
                note="partial run: device hung after these stages",
            )
            if state.get("roofline"):
                prov["roofline_tops"] = round(state["roofline"] / 1e12, 3)
            elif lm and lm.get("roofline_tops"):
                prov["roofline_tops"] = lm["roofline_tops"]
            _write_last_measured(prov)
        else:
            line = {
                "metric": "MH/s/chip md5 pow search (device hung mid-bench)",
                "value": 0.0,
                "unit": "MH/s",
                "vs_baseline": 0.0,
            }
            if lm:
                line["last_measured"] = lm
        print(json.dumps(line), flush=True)
        print(f"[bench] device made no progress for {stale:.0f}s "
              f"mid-run; measured stages: {sorted(snap)}", file=sys.stderr)
        # the main thread is stuck in the dispatch: only _exit ends it
        os._exit(1)

    WATCHDOG.start(420.0, on_hang=_hang_bailout)

    from distpow_tpu.models.registry import get_hash_model
    from distpow_tpu.ops.search_step import (
        XLA_SERVING_COMPILE_IMPRACTICAL,
        build_search_step,
        cached_search_step,
    )
    from distpow_tpu.parallel.search import launch_steps_for

    model = get_hash_model("md5")
    nonce = b"\x01\x02\x03\x04"
    difficulty = 8
    chunks = 8192  # x 256 thread bytes = 2^21 candidates per sub-batch
    # the launch multiplier a serving worker would use for width-4 chunks
    k = launch_steps_for(4, chunks, 256)

    # ---- Phase A: md5 headline paths ---------------------------------

    def serving_builder():
        # the serving path: nonce/difficulty/partition are runtime
        # operands; k sub-batches per dispatch amortize the round trip
        step = cached_search_step(
            nonce, 4, difficulty, 0, 256, chunks, model.name, b"", k
        )
        return step, chunks * 256 * k

    def xla_static_builder():
        step = build_search_step(
            nonce, 4, difficulty, 0, 256, chunks, model, launch_steps=k
        )
        return step, chunks * 256 * k

    rates["serving"] = device_rate(
        serving_builder, f"serving (dynamic) step, k={k}"
    )
    rates["xla-static"] = device_rate(
        xla_static_builder, f"static-compiled step, k={k}"
    )

    # One pallas builder import for the kernel benches; None = pallas
    # unavailable on this backend, each block then skips itself.
    try:
        from distpow_tpu.ops.md5_pallas import (
            MODEL_GEOMETRY,
            build_pallas_search_step,
        )
    except Exception as exc:
        print(f"[bench] pallas path unavailable: {exc}", file=sys.stderr)
        build_pallas_search_step = None
        MODEL_GEOMETRY = {}
    # launch multiplier shared by the slower-hash benches (1<<28 budget
    # vs the md5 benches' 1<<30: same wall time per timed window)
    k28 = launch_steps_for(4, chunks, 256, 1 << 28)

    if build_pallas_search_step is not None:
        try:
            def pallas_builder():
                # same launch amortization as the XLA paths: k
                # sub-batches per dispatch via the kernel's extended
                # sequential grid
                step = build_pallas_search_step(
                    nonce, 4, difficulty, 0, 256, chunks, launch_steps=k
                )
                return step, chunks * 256 * k

            rates["pallas"] = device_rate(
                pallas_builder, f"pallas kernel, k={k}"
            )
        except Exception as exc:
            print(f"[bench] pallas bench failed: {exc}", file=sys.stderr)

    # ---- Phase B: every model's PRODUCTION path ----------------------
    # The Pallas kernel is what a TPU config actually serves for every
    # non-md5 model (the XLA serving step is a diagnostic, and for
    # sha512/sha384/sha3/blake2b it is unusable or HBM-bound —
    # docs/KERNELS.md).  These lines ARE the registry's standing; they
    # run before any anchor or diagnostic so one healthy ~2-minute
    # window records all eight models (VERDICT r4 item 1).
    if build_pallas_search_step is not None:
        for mname in OTHER_MODELS:
            if mname not in MODEL_GEOMETRY:
                # no kernel tile for this model: the pallas backends
                # fall back to the XLA step, so there is nothing
                # separate to measure — and a guaranteed 'failed' line
                # would bury real regressions (review r4)
                print(f"[bench] {mname}: no pallas tile "
                      f"(XLA fallback path)", file=sys.stderr)
                continue
            try:
                def pallas_b(mname=mname):
                    step = build_pallas_search_step(
                        nonce, 4, difficulty, 0, 256, chunks,
                        model_name=mname, launch_steps=k28,
                    )
                    return step, chunks * 256 * k28

                rates[f"{mname}-pallas"] = device_rate(
                    pallas_b, f"{mname} pallas kernel, k={k28}"
                )
            except Exception as exc:
                print(f"[bench] {mname} pallas bench failed: {exc}",
                      file=sys.stderr)

    # ---- Phase C: anchors (roofline + native CPU baselines) ----------
    # Utilization vs a MEASURED VPU integer roofline (VERDICT r2 weak #4:
    # round 2's 7.7 Tops/s denominator was back-derived from the hash
    # rates themselves; this one is measured by a pure rotate-add chain
    # at the serving footprint).  Ops/hash figures are XLA's own
    # cost_analysis() flop counts on the optimized serving program at
    # difficulty 8 nibbles (mask-word DCE included), carried as
    # ``HashModel.cost_ops`` (models/registry.py) since the backends'
    # launch-budget scaling consumes them too.  Derivation per model:
    # md5/sha256 measured on the TPU compile; sha1/ripemd160/sha512 on
    # an XLA:CPU compile with the unrolled compress forced (the method
    # re-reproduces the TPU sha256 figure exactly); sha3_256 from the
    # unrolled keccak TILE (there is no unrolled XLA serving form — the
    # tile IS the unrolled graph, same convention).  The md5 hand count
    # (~650, rotate=3-ops) brackets the same ballpark.  MXU does not
    # apply: the workload has no matmuls.
    MD5_OPS_PER_HASH = get_hash_model("md5").cost_ops
    try:
        roofline = measured_vpu_roofline()
        state["roofline"] = roofline
    except Exception as exc:  # degrade like the rate sections above
        print(f"[bench] roofline microbenchmark failed: {exc}",
              file=sys.stderr)
        roofline = None

    # CPU single-worker baseline (reference config 1 stand-in)
    baseline = None
    try:
        from distpow_tpu.backends import native_miner

        lib = native_miner.load_library()
        import ctypes

        tb = bytes(range(256))
        hashes = ctypes.c_uint64(0)
        secret = ctypes.create_string_buffer(16)
        n = 1 << 21
        t0 = time.time()
        lib.distpow_search_range(
            nonce, len(nonce), 32, 0, tb, len(tb), 4, 1 << 24, n // 256,
            1, None, ctypes.byref(hashes), secret,
        )
        dt = time.time() - t0
        baseline = hashes.value / dt
        state["baseline"] = baseline
        print(f"[bench] native 1-thread CPU baseline: "
              f"{baseline / 1e6:.2f} MH/s", file=sys.stderr)
        # sha256 CPU baseline (algo=1): anchors the sha256 kernel
        # rate's vs-CPU ratio the way the md5 baseline anchors the
        # headline.  Own try/except: a failure in this DIAGNOSTIC must
        # not fall into the outer except and replace the already-valid
        # md5 native baseline with the ~50x-slower hashlib fallback
        # (which would inflate the headline vs_baseline).
        try:
            hashes_s = ctypes.c_uint64(0)
            t0 = time.time()
            lib.distpow_search_range(
                nonce, len(nonce), 64, 1, tb, len(tb), 4, 1 << 24,
                (1 << 20) // 256, 1, None, ctypes.byref(hashes_s), secret,
            )
            sha_base = hashes_s.value / (time.time() - t0)
            print(f"[bench] native 1-thread sha256 CPU baseline: "
                  f"{sha_base / 1e6:.2f} MH/s", file=sys.stderr)
            if "sha256-pallas" in rates and sha_base > 0:
                print(f"[bench] sha256 kernel vs 1-thread CPU: "
                      f"{rates['sha256-pallas'] / sha_base:.0f}x",
                      file=sys.stderr)
        except Exception as exc:
            print(f"[bench] sha256 CPU baseline failed: {exc}",
                  file=sys.stderr)
    except Exception as exc:
        print(f"[bench] native baseline unavailable ({exc}); "
              f"falling back to hashlib", file=sys.stderr)
        import hashlib

        t0 = time.time()
        count = 200_000
        for i in range(count):
            hashlib.md5(nonce + i.to_bytes(5, "little")).digest()
        baseline = count / (time.time() - t0)
        state["baseline"] = baseline
        print(f"[bench] hashlib CPU baseline: {baseline / 1e6:.2f} MH/s",
              file=sys.stderr)

    if roofline:
        md5_best = max(v for lbl, v in rates.items() if lbl in MD5_LABELS)
        print(f"[bench] VPU utilization (md5 best path): "
              f"{md5_best * MD5_OPS_PER_HASH / 1e12:.2f} Tops/s of "
              f"{roofline / 1e12:.2f} Tops/s measured roofline "
              f"= {100 * md5_best * MD5_OPS_PER_HASH / roofline:.0f}% "
              f"(at {MD5_OPS_PER_HASH} XLA-counted ops/hash)",
              file=sys.stderr)
        for tag in OTHER_MODELS:
            ops = get_hash_model(tag).cost_ops
            tag_rates = [v for l, v in rates.items()
                         if l.split("-")[0] == tag]
            if not tag_rates:
                continue
            r_best = max(tag_rates)
            print(f"[bench] VPU utilization ({tag} best path): "
                  f"{r_best * ops / 1e12:.2f} Tops/s of "
                  f"{roofline / 1e12:.2f} Tops/s measured roofline "
                  f"= {100 * r_best * ops / roofline:.0f}% "
                  f"(at {ops} XLA-counted ops/hash)",
                  file=sys.stderr)

    # ---- Phase D: e2e wall-clock solves (deadline-gated) -------------
    # end-to-end wall-clock to first valid nonce (BASELINE.md's second
    # metric): warm the layout-keyed programs the way a booted worker does
    # (WorkerConfig.WarmupNonceLens), then solve fresh nonces at 32-bit
    # difficulty — steady-state serving latency, driver + verification
    # included.  (The full per-model latency table lives in
    # scripts/e2e_models.py; these two backends pin the headline paths.)
    if time.time() > deadline:
        print(f"[bench] deadline exceeded before e2e solves; skipping "
              f"phases D-E (registry standing already measured)",
              file=sys.stderr)
    else:
        try:
            from distpow_tpu.backends import JaxBackend
            from distpow_tpu.models import puzzle

            backend = JaxBackend(batch_size=1 << 21)
            t0 = time.time()
            backend.warmup([4], [0, 1, 2, 3, 4])
            print(f"[bench] worker warmup (len-4 nonces, widths 0-4): "
                  f"{time.time() - t0:.1f}s one-time", file=sys.stderr)
            for nonce_e2e, d in ((b"\x13\x57\x9b\xdf", 8), (b"\x24\x68\xac\xe0", 8)):
                t0 = time.time()
                secret = backend.search(nonce_e2e, d, list(range(256)))
                dt = time.time() - t0
                assert secret is not None
                assert puzzle.check_secret(nonce_e2e, secret, d)
                print(f"[bench] e2e diff={4 * d}bit solve of {nonce_e2e.hex()}: "
                      f"secret={secret.hex()} in {dt:.2f}s wall-clock",
                      file=sys.stderr)
        except Exception as exc:
            print(f"[bench] e2e solve failed: {exc}", file=sys.stderr)

        # the same e2e solve through the Pallas-kernel backend (VERDICT r1
        # item 1: the kernel as a production path, not a showpiece).  The
        # backend is warmed exactly as a booted worker warms it (the kernel
        # program is layout-keyed, so the zero-nonce warmup covers every
        # fresh nonce of the same length) — round 2's 18s figure was this
        # same solve timed stone-cold, i.e. it measured Mosaic compiles, not
        # the serving path (VERDICT r2 weak #1).
        try:
            from distpow_tpu.backends import get_backend
            from distpow_tpu.models import puzzle

            pb = get_backend("pallas", batch_size=1 << 21)
            t0 = time.time()
            pb.warmup([4], [0, 1, 2, 3, 4])
            print(f"[bench] pallas worker warmup (len-4 nonces, widths 0-4): "
                  f"{time.time() - t0:.1f}s one-time", file=sys.stderr)
            for nonce_e2e, d in ((b"\x35\x79\xbd\xf1", 8), (b"\x46\x8a\xce\x02", 8)):
                t0 = time.time()
                secret = pb.search(nonce_e2e, d, list(range(256)))
                dt = time.time() - t0
                assert secret is not None
                assert puzzle.check_secret(nonce_e2e, secret, d)
                print(f"[bench] e2e diff={4 * d}bit solve via pallas backend: "
                      f"secret={secret.hex()} in {dt:.2f}s wall-clock "
                      f"(warm, steady-state)", file=sys.stderr)
        except Exception as exc:
            print(f"[bench] pallas e2e solve failed: {exc}", file=sys.stderr)

    # ---- Phase E: diagnostic XLA serving lines (deadline-gated) ------
    # The XLA serving step per model, for the kernel-vs-fusion story in
    # docs/KERNELS.md.  Strictly diagnostic: no config serves these
    # paths on TPU, so they run LAST, and the HBM-bound ones (keccak /
    # blake2 loop forms at single-digit MH/s) get a candidate budget
    # derived from their last measured rate targeting a ~3 s window —
    # bench7 spent 78.7 s on sha3's line at the shared budget.
    # sha512/sha384 are skipped
    # outright: their fused XLA step is impractical to compile on this
    # backend (>30 min observed, r4c; the sweep artifact records the one
    # completed measurement at 12.4 MH/s vs the kernel's 538.9).
    prev_rates = (last_measured or {}).get("rates_mhs") or {}
    # diagnostic order: the budget-capped HBM-bound reconciliation
    # targets first; then sha256d — its composed serving step's FIRST
    # compile cost is unknown on this backend (review r5), so it runs
    # while the deadline check still admits it (warming the persistent
    # cache for the sweep) and, if the compile proves sha512-class, the
    # 1800 s compile grace expires into the hang bailout, which
    # SALVAGES every already-measured stage into provenance rather
    # than losing the run; the well-characterized serving lines close
    # the tail
    for mname in HBM_BOUND_SERVING + ("sha256d",) + tuple(
            m for m in OTHER_MODELS
            if m not in HBM_BOUND_SERVING and m != "sha256d"):
        if mname in XLA_SERVING_COMPILE_IMPRACTICAL:
            print(f"[bench] {mname}: serving line skipped (XLA step "
                  f"compile impractical on this backend; kernel-only "
                  f"model — docs/KERNELS.md)", file=sys.stderr)
            continue
        if time.time() > deadline:
            print(f"[bench] deadline exceeded; skipping remaining "
                  f"diagnostic serving lines (from {mname})",
                  file=sys.stderr)
            break
        if mname in HBM_BOUND_SERVING:
            # rate-derived budget: ~3 s of candidates at the last
            # measured rate, floored at one sub-batch, capped at 2^24.
            # A recorded 0.0 means "measured, pathologically slow" —
            # clamp it up to the floor budget, do NOT fall back to the
            # no-history default (review r5: `prev or 4.0` would hand a
            # 0.004 MH/s path a 12.6M-candidate first window)
            prev = prev_rates.get(f"{mname}-serving")
            assumed = 4.0 if prev is None else max(prev, 0.01)
            budget = int(min(
                1 << 24,
                max(chunks * 256, assumed * 1e6 * 3.0),
            ))
            ks = launch_steps_for(4, chunks, 256, budget)
            min_s, it0 = 1.0, 1
        else:
            ks, min_s, it0 = k28, 2.0, 4
        try:
            def serving_b(mname=mname, ks=ks):
                step = cached_search_step(
                    nonce, 4, difficulty, 0, 256, chunks, mname, b"", ks
                )
                return step, chunks * 256 * ks

            rates[f"{mname}-serving"] = device_rate(
                serving_b, f"{mname} serving step, k={ks}",
                min_seconds=min_s, start_iters=it0,
            )
        except Exception as exc:
            print(f"[bench] {mname} serving bench failed: {exc}",
                  file=sys.stderr)

    # ---- Control-plane stage (CPU, deadline-gated) -------------------
    # the RPC data plane's standing row (ISSUE 5): pure CPU, so it runs
    # even on rounds where the device half degraded — but after every
    # device stage, and only while the deadline still admits it
    control_plane = None
    if os.environ.get("BENCH_CONTROL_PLANE") != "0" and \
            time.time() <= deadline:
        try:
            control_plane = control_plane_stage()
        except Exception as exc:
            print(f"[bench] control-plane stage failed: {exc}",
                  file=sys.stderr)

    # ---- Load-SLO stage (CPU, deadline-gated) ------------------------
    # the open-loop + cluster-SLO row (ISSUE 8): python backends only —
    # like the control-plane stage it never touches jax
    load_slo = None
    if os.environ.get("BENCH_LOAD_SLO") != "0" and \
            time.time() <= deadline:
        try:
            load_slo = load_slo_stage()
        except Exception as exc:
            print(f"[bench] load-slo stage failed: {exc}",
                  file=sys.stderr)

    # ---- Membership stage (CPU, deadline-gated) ----------------------
    # the elastic-fleet row (ISSUE 12): lease-expiry reassignment vs
    # the probe baseline + straggler hedging — python backends only,
    # so it runs on healthy rounds too (same carry-forward rationale
    # as the load-slo stage)
    membership = None
    if os.environ.get("BENCH_MEMBERSHIP") != "0" and \
            time.time() <= deadline:
        try:
            membership = membership_stage()
        except Exception as exc:
            print(f"[bench] membership stage failed: {exc}",
                  file=sys.stderr)

    # ---- Forensics-overhead stage (CPU, deadline-gated) --------------
    # the request-forensics row (ISSUE 14): serving throughput with
    # spans+exemplars on vs off — python backends only, so it runs on
    # healthy rounds too (same carry-forward rationale as the load-slo
    # stage); the 5% acceptance bound is asserted inside the stage
    forensics = None
    if os.environ.get("BENCH_FORENSICS") != "0" and \
            time.time() <= deadline:
        try:
            forensics = forensics_overhead_stage()
        except Exception as exc:
            print(f"[bench] forensics stage failed: {exc}",
                  file=sys.stderr)

    # ---- Cluster-scale stage (CPU, deadline-gated) -------------------
    # the coordinator scale-out row (ISSUE 15): aggregate open-loop
    # solves/s across 1/2/4-member pools — stub backends only, so it
    # runs on healthy rounds too (same carry-forward rationale as the
    # load-slo stage); the speedup floors are asserted inside the stage
    cluster_scale = None
    if os.environ.get("BENCH_CLUSTER_SCALE") != "0" and \
            time.time() <= deadline:
        try:
            cluster_scale = cluster_scale_stage()
        except Exception as exc:
            print(f"[bench] cluster-scale stage failed: {exc}",
                  file=sys.stderr)

    # ---- Cache-HA stage (CPU, deadline-gated) ------------------------
    # the replicated-dominance-cache row (ISSUE 16): survivor repeat
    # hit ratio after a member kill, replication on vs off — python
    # backends only, so it runs on healthy rounds too (same
    # carry-forward rationale as the load-slo stage); the hit-ratio
    # floors are asserted inside the stage
    cache_ha = None
    if os.environ.get("BENCH_CACHE_HA") != "0" and \
            time.time() <= deadline:
        try:
            cache_ha = cache_ha_stage()
        except Exception as exc:
            print(f"[bench] cache-ha stage failed: {exc}",
                  file=sys.stderr)

    # ---- Mesh-serving stage (CPU subprocesses, deadline-gated) -------
    # the kernel-lane scale-out row (ISSUE 20): scheduler solves/s at
    # 4 vs 1 virtual CPU devices — each arm runs in its own CPU-pinned
    # subprocess (the device count is fixed at backend init), so the
    # chip this process holds is never touched; the >=2x
    # speedup floor is asserted into the stage's ok
    mesh_serving = None
    if os.environ.get("BENCH_MESH_SERVING") != "0" and \
            time.time() <= deadline:
        try:
            mesh_serving = mesh_serving_stage(
                timeout_s=min(600.0, max(1.0, deadline - time.time()))
            )
        except Exception as exc:
            print(f"[bench] mesh-serving stage failed: {exc}",
                  file=sys.stderr)

    # ---- Final line ---------------------------------------------------
    line, prov = finalize_record(rates, last_measured, baseline,
                                 control_plane=control_plane,
                                 load_slo=load_slo,
                                 membership=membership,
                                 forensics=forensics,
                                 cluster_scale=cluster_scale,
                                 cache_ha=cache_ha,
                                 mesh_serving=mesh_serving)
    # the measured roofline rides in provenance: the generated
    # registry-standing table (scripts/gen_registry_table.py) derives
    # utilization percentages from it.  prov is None when no md5 stage
    # measured (finalize_record's hung-device guard): emit the line but
    # leave last_measured.json untouched.
    if prov is not None and roofline:
        prov["roofline_tops"] = round(roofline / 1e12, 3)
    elif prov is not None and last_measured and last_measured.get("roofline_tops"):
        prov["roofline_tops"] = last_measured["roofline_tops"]
    for lbl, info in line.get("suspect_readings", {}).items():
        print(f"[bench] SUSPECT reading for {lbl}: "
              f"{info['measured_mhs']} MH/s vs last measured "
              f"{info['last_measured_mhs']} ({info['ratio']}x) — "
              f"provenance keeps the previous value "
              f"(BENCH_ACCEPT_ANOMALIES=1 to override)", file=sys.stderr)
    # a run where NO production kernel line was measured (Mosaic import
    # break, every Phase B stage failing) must not look like a healthy
    # refresh: everything non-md5 would be carried forward silently
    if not any(l.endswith("-pallas") for l in rates):
        print("[bench] WARNING: zero production kernel lines measured "
              "for non-md5 models this run — non-md5 provenance is "
              "entirely carried forward", file=sys.stderr)
        line["production_gap"] = True
        if prov is not None:
            prov["production_gap"] = True

    # disarm BEFORE the real JSON line: the hang bailout must never
    # print a second line after a successful run
    WATCHDOG.stop()
    if prov is not None:
        _write_last_measured(prov)
    else:
        print("[bench] no md5 stage measured: provenance NOT refreshed",
              file=sys.stderr)
    print(json.dumps(line))


if __name__ == "__main__":
    main()
