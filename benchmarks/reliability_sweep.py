"""BER sweep: wrong-result-rate gate for the reliability tier.

Two sweeps over the §IV-C fault pipeline, emitted as exact counters so
``check_regression.py`` can gate them (``reliability_*`` metrics match the
committed baseline bit-for-bit, and the two ``HARD_ZEROS`` must be zero in
every fresh run, baseline or not):

* **Verified sweep** — retention ages 0/45/90 days at ``base_ber=1e-4``
  push pages through the whole verdict ladder (age 0: mostly CLEAN opens;
  age 45: read-retries, ECC fallbacks and refresh marks; age 90: raw error
  counts beyond ``t_correctable``, surfacing as typed per-op errors).  A
  fused-lookup YCSB replay runs per backend per age under the *same* fault
  seed; the gate is (a) zero wrong results against the analytic oracle —
  every read either returns the exact stored value or a typed
  ``UncorrectableReadError``, never a silently wrong/missing one — and
  (b) bit-identical per-op outcomes across scalar/sharded.

* **Unverified sweep** — clean storage, transient comparator noise only
  (``sense_ber=5e-4``), verification and miss-fallback disabled.  This is
  the approximate-search operating point the paper's §IV-C3 voting targets:
  the measured wrong-op rate must be nonzero at ``vote_k=1`` (proving the
  sweep actually exercises the noise path), must shrink under 3-pass
  voting, and must sit under ``sense_false_positive_bound`` (+3-sigma
  sampling slack — the bound is per-op, the measurement is 240 ops).

Run from the repo root:  PYTHONPATH=src python -m benchmarks.reliability_sweep
"""
from __future__ import annotations

import math

import numpy as np

from benchmarks.common import emit, write_bench_json
from repro.backend import make_backend
from repro.core.engine import SimChipArray
from repro.reliability import (FaultModel, ReliabilityPolicy,
                               ReliabilityState,
                               sense_false_negative_bound,
                               sense_false_positive_bound)
from repro.frontend import RunConfig, replay
from repro.workload.ycsb import generate

N_QUERIES = 240
N_KEY_PAGES = 12
N_CHIPS = 4
FAULT_SEED = 11
BASE_BER = 1e-4
SENSE_BER = 5e-4
AGES_DAYS = (0, 45, 90)
BACKENDS = ("scalar", "sharded")


def _workload():
    return generate(N_QUERIES, n_key_pages=N_KEY_PAGES, read_ratio=1.0,
                    alpha=0.9, seed=7)


def _expected_values(wl) -> np.ndarray:
    """Oracle: read-only stream, so every op's answer is the initial value
    the runner programs for key id k — ((k + 1) * phi64) | 1."""
    return (wl.keys.astype(np.uint64) + np.uint64(1)) \
        * np.uint64(0x9E3779B97F4A7C15) | np.uint64(1)


def _run(wl, backend_name: str, policy: ReliabilityPolicy,
         fault: FaultModel):
    arr = SimChipArray(n_chips=N_CHIPS,
                       pages_per_chip=max(wl.n_index_pages // N_CHIPS + 1,
                                          8),
                       device_seed=3)
    kw = {"use_kernel": False} if backend_name == "sharded" else {}
    rel = ReliabilityState(policy, fault)
    res = replay(wl, make_backend(backend_name, arr, **kw),
                 RunConfig.reliable(rel, burst=64, fused=True))
    return res, rel


def verified_sweep() -> None:
    wl = _workload()
    oracle = _expected_values(wl)
    policy = ReliabilityPolicy(verify_hits=True, fallback_on_miss=True,
                               vote_k=3)
    wrong = 0
    mismatch = 0
    for age in AGES_DAYS:
        fault = FaultModel(seed=FAULT_SEED, base_ber=BASE_BER,
                           retention_days=float(age), sense_ber=2e-4)
        runs = {}
        for name in BACKENDS:
            res, rel = _run(wl, name, policy, fault)
            runs[name] = res
            # Wrong result = anything that is neither the exact oracle
            # value nor a typed error: a silent miss or a wrong value.
            ok_hit = res.read_hits & (res.read_values == oracle)
            wrong += int(np.sum(~(ok_hit | res.read_errors)))
            if name == "scalar":
                emit(f"reliability_retries_age{age}", rel.stats.retries,
                     f"ber={fault.raw_ber():.2e}_vote_k={policy.vote_k}")
                emit(f"reliability_fallback_reads_age{age}",
                     rel.stats.fallback_reads,
                     "full_page_storage_mode_reads_open_plus_resolve")
                emit(f"reliability_uncorrectable_age{age}",
                     rel.stats.uncorrectable,
                     "outer_code_failures_as_typed_errors")
                emit(f"reliability_refreshes_age{age}", res.refreshes,
                     "stale_pages_rewritten_via_deferred_program")
        ref = runs["scalar"]
        for name in BACKENDS[1:]:
            r = runs[name]
            mismatch += int(np.sum(r.read_values != ref.read_values))
            mismatch += int(np.sum(r.read_hits != ref.read_hits))
            mismatch += int(np.sum(r.read_errors != ref.read_errors))
    # Hard gates (also re-checked by check_regression's HARD_ZEROS).
    assert wrong == 0, \
        f"{wrong} silently wrong results escaped the verified pipeline"
    assert mismatch == 0, \
        f"{mismatch} per-op divergences between backends under one seed"
    emit("reliability_wrong_results_verified", wrong,
         f"ages={AGES_DAYS}_x_backends={BACKENDS}_vs_analytic_oracle")
    emit("reliability_backend_mismatch", mismatch,
         "per_op_value+hit+error_diffs_vs_scalar_reference")


def unverified_sweep() -> None:
    wl = _workload()
    oracle = _expected_values(wl)
    rates = {}
    for vote_k in (1, 3):
        policy = ReliabilityPolicy(verify_hits=False,
                                   fallback_on_miss=False, vote_k=vote_k)
        fault = FaultModel(seed=FAULT_SEED, base_ber=0.0,
                           sense_ber=SENSE_BER)
        res, rel = _run(wl, "scalar", policy, fault)
        fp_ops = int(np.sum(res.read_hits & (res.read_values != oracle)))
        fn_ops = int(np.sum(~res.read_hits & ~res.read_errors))
        wrong_ops = fp_ops + fn_ops
        rates[vote_k] = wrong_ops / N_QUERIES
        bound = sense_false_positive_bound(SENSE_BER, vote_k) \
            + sense_false_negative_bound(SENSE_BER, vote_k)
        # The bound is a per-op probability; the measurement is N_QUERIES
        # deterministic Bernoulli draws, so allow 3-sigma sampling slack.
        slack = 3.0 * math.sqrt(bound * (1.0 - bound) / N_QUERIES)
        assert rates[vote_k] <= bound + slack, \
            (f"unverified wrong-op rate {rates[vote_k]:.4f} above analytic "
             f"bound {bound:.4f} (+{slack:.4f} slack) at vote_k={vote_k}")
        emit(f"reliability_fp_ops_unverified_k{vote_k}", fp_ops,
             f"sense_ber={SENSE_BER}_bound={bound:.4f}")
        emit(f"reliability_fn_ops_unverified_k{vote_k}", fn_ops,
             f"sense_ber={SENSE_BER}_vote_k={vote_k}")
    assert rates[1] > 0.0, \
        "unverified vote_k=1 run measured zero wrong ops — the sweep is " \
        "not exercising the sense-noise path"
    assert rates[3] <= rates[1], \
        f"3-pass voting did not reduce the wrong-op rate " \
        f"({rates[3]:.4f} > {rates[1]:.4f})"


def main() -> None:
    verified_sweep()
    unverified_sweep()
    write_bench_json("reliability_sweep")


if __name__ == "__main__":
    main()
