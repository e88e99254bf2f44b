"""The comparison that decides ``correct``.

Made after the window has closed, on what its LAST slice call
produced — the call's returned candidate list and single-pulse
events, and the raw per-pass candidates that call handed to the
harness's ``PassDumpStore`` at each pass's end — against the plain
reference (``reference.py``).  Each number compared has a limit of its
own, stated in the configuration file with the readings it was set
from (``PERF.md``); every number is printed beside its limit.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from benchmark.harness import reference as ref
from benchmark.harness.generate import Pulsar, seed_rng

LO_STAGES = (1, 2, 4, 8, 16)


@dataclasses.dataclass
class Number:
    name: str
    value: float
    limit: float
    n: int = 1            # how many answers went into it

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit

    def as_dict(self) -> dict:
        return {"name": self.name, "value": float(self.value),
                "limit": float(self.limit), "n": int(self.n),
                "ok": bool(self.ok)}


def pass_table(plan) -> list[dict]:
    """The slice's passes in the order ``search_block`` runs them."""
    out = []
    for step in plan:
        for p in step.passes():
            out.append({"subdm": p.subdm, "dms": np.asarray(p.dms),
                        "downsamp": step.downsamp,
                        "lodm": p.lodm,
                        "hidm": p.lodm + step.sub_dmstep})
    return out


# -------------------------------------------------------------- recovery

def _harmonic_ratios(max_int: int = 4):
    seen = set()
    for a in range(1, max_int + 1):
        for b in range(1, max_int + 1):
            if math.gcd(a, b) == 1:
                seen.add((a, b))
    return sorted(seen, key=lambda ab: (max(ab), ab))


def recovery(cands, psr: Pulsar, T_s: float, passes: list[dict],
             hi: bool, tol: dict) -> list[Number]:
    """The injected pulsar among the returned candidates: frequency
    within the sifter's harmonic tolerance of the truth's mean
    frequency times a low harmonic ratio, DM inside the pass that
    holds the truth, and with hi-accel on the drift within the z
    grid's spacing of the injected one (scaled by the same ratio)."""
    f_true = psr.mean_freq_hz(T_s)
    home = next(p for p in passes if p["lodm"] <= psr.dm < p["hidm"])
    ratios = [a / b for a, b in _harmonic_ratios()]
    best = None             # (sigma, frequency error, z error)
    for c in cands:
        if not (home["lodm"] - 1e-6 <= c.dm < home["hidm"]):
            continue
        for ratio in ratios:
            ferr = abs(c.freq_hz / (f_true * ratio) - 1.0)
            if ferr < tol["period_frac_err"]:
                zerr = abs(c.z - psr.z * ratio) / max(1.0, ratio)
                if best is None or c.sigma > best[0]:
                    best = (c.sigma, ferr, zerr)
                break
    if best is None:
        return [Number("pulsar_missing", 1.0, 0.0)]
    out = [Number("pulsar_missing", 0.0, 0.0),
           Number("pulsar_period_frac_err", best[1],
                  tol["period_frac_err"])]
    if hi:
        out.append(Number("pulsar_z_err_bins", best[2],
                          tol["z_err_bins"]))
    return out


# ---------------------------------------------------------------- sample

def _pick(rng, idx: np.ndarray, weight: np.ndarray, top: int, more: int):
    """`top` strongest of idx plus up to `more` others drawn from rng."""
    if len(idx) == 0:
        return idx
    order = idx[np.argsort(-weight[idx], kind="stable")]
    rest = order[top:]
    extra = (rng.choice(rest, size=min(more, len(rest)), replace=False)
             if len(rest) else rest)
    return np.concatenate([order[:top], extra]).astype(np.int64)


def sample_dms(rng, dump: dict, pas: dict, truth_dm: float | None,
               n_dms: int) -> list[float]:
    """The truth's trial (when this pass holds it) plus up to n_dms
    trials drawn from those the call reported anything at."""
    dms = pas["dms"]
    have = set(np.round(dump["cands"]["dm"], 6)) | \
        set(np.round(dump["events"]["dm"], 6))
    pool = sorted(d for d in have if np.any(np.isclose(dms, d, atol=1e-6)))
    chosen: list[float] = []
    if truth_dm is not None:
        chosen.append(float(dms[np.argmin(np.abs(dms - truth_dm))]))
    pool = [d for d in pool if not any(abs(d - c) < 1e-6 for c in chosen)]
    if pool:
        chosen.extend(float(d) for d in rng.choice(
            pool, size=min(n_dms, len(pool)), replace=False))
    return chosen


# ------------------------------------------------------------ the check

def check(cell, plan, psr: Pulsar, call, block, seed: int,
          control: bool = False) -> dict:
    """-> {"correct": bool, "numbers": [...], "control": [...]}.

    `control` also evaluates the reference in the next lower precision
    at the same points and reports ITS gaps from the float32
    reference, held to the same limits: a sound check fails them."""
    tol = cell.config["tolerances"]
    sp = cell.config["search_params"]
    passes = pass_table(plan)
    T_s_full = ref.choose_n(cell.nsamp) * cell.dt
    nsub = int(cell.config["nsub"])
    hi = cell.run_hi_accel
    rng = seed_rng(seed + 1)
    numbers: list[Number] = []

    # 1. every trial searched, no degraded or rescued mode
    numbers.append(Number("trials_not_searched",
                          float(call.ntrials_given - call.ntrials_done),
                          0.0, n=call.ntrials_given))
    numbers.append(Number("degraded_or_rescued_flags",
                          float(len(call.degraded) + len(call.rescued)),
                          0.0))

    # 2. the injected pulsar is among the returned candidates
    cands, _folded, _events, _n = call.result
    numbers.extend(recovery(cands, psr, T_s_full, passes, hi, tol))

    # 3. powers and SNRs at sampled answers against the plain reference
    gaps = {"lo_power_gap": [], "hi_power_gap": [], "sp_snr_gap": []}
    cgaps = {k: [] for k in gaps}
    missing = checked = 0
    hi_ref = ref.HiStage(float(sp["hi_accel_zmax"]))
    hi_low = ref.HiStage(float(sp["hi_accel_zmax"]), lower=True)
    for pas, dump in zip(passes, call.dumps):
        holds_truth = pas["lodm"] <= psr.dm < pas["hidm"]
        dms = sample_dms(rng, dump, pas, psr.dm if holds_truth else None,
                         int(tol["sample_dms_per_pass"]))
        if not dms:
            continue
        ds = pas["downsamp"]
        chan_sh, sub_sh = ref.pass_shifts(cell.freqs, nsub, pas["subdm"],
                                          dms, cell.dt, ds)
        subb = ref.form_subbands(block, chan_sh, nsub, ds)
        nfft = ref.choose_n(int(subb.shape[1]))
        cd, ev = dump["cands"], dump["events"]
        q_all = np.rint(2.0 * cd["r"]).astype(np.int64)
        for k, dm in enumerate(dms):
            series = ref.dedisperse_one(subb, sub_sh[k])
            X = ref.whitened_spectrum(series, nfft)
            Xl = ref.whitened_spectrum(series, nfft, lower=True) \
                if control else None
            at_dm = np.isclose(cd["dm"], dm, atol=1e-6)
            for stage, sel in (("lo", at_dm & (cd["z"] == 0.0)),
                               ("hi", at_dm & (cd["z"] != 0.0))):
                for i in _pick(rng, np.flatnonzero(sel), cd["power"],
                               3, 3):
                    q, H = int(q_all[i]), int(cd["numharm"][i])
                    if stage == "lo":
                        want = ref.lo_power(X, q, H)
                        low = ref.lo_power(Xl, q, H) if control else None
                    else:
                        z = float(cd["z"][i])
                        want = hi_ref.power(X, q, z, H)
                        low = hi_low.power(Xl, q, z, H) if control else None
                    name = f"{stage}_power_gap"
                    gaps[name].append(abs(cd["power"][i] - want) / want)
                    if control:
                        cgaps[name].append(abs(low - want) / want)
            if holds_truth and k == 0:
                # the reference's own best zero-drift candidate of each
                # harmonic stage must be in the program's list
                floors = tol["lo_best_min_power"]
                lo_sel = at_dm & (cd["z"] == 0.0)
                for H, (q, pw) in ref.lo_stage_best(X, LO_STAGES).items():
                    if H > int(sp["lo_accel_numharm"]) \
                            or pw < float(floors[str(H)]):
                        continue
                    checked += 1
                    hit = lo_sel & (cd["numharm"] == H) \
                        & (np.abs(q_all - q) <= 1)
                    missing += 0 if hit.any() else 1
            ev_idx = np.flatnonzero(np.isclose(ev["dm"], dm, atol=1e-6))
            if len(ev_idx):
                norm = ref.normalized_series(series)
                norml = ref.normalized_series(series, lower=True) \
                    if control else None
                for i in _pick(rng, ev_idx, ev["sigma"], 2, 3):
                    s, w = int(ev["sample"][i]), int(ev["downfact"][i])
                    want = ref.boxcar_snr(norm, s, w)
                    gaps["sp_snr_gap"].append(abs(ev["sigma"][i] - want))
                    if control:
                        cgaps["sp_snr_gap"].append(
                            abs(ref.boxcar_snr(norml, s, w) - want))
        del subb
    numbers.append(Number("lo_best_missing", float(missing), 0.0,
                          n=checked))
    # the kinds of answer this cell's main layers must have produced at
    # the sampled trials: none to compare is then itself a failure
    required = {"sp_snr_gap", "hi_power_gap" if hi else "lo_power_gap"}
    for name, vals in gaps.items():
        if name == "hi_power_gap" and not hi:
            continue
        empty = math.inf if name in required else 0.0
        numbers.append(Number(name, max(vals) if vals else empty,
                              float(tol[name]), n=len(vals)))
    out = {"correct": all(n.ok for n in numbers),
           "numbers": [n.as_dict() for n in numbers]}
    if control:
        out["control"] = [
            Number(name, max(vals), float(tol[name]), n=len(vals)).as_dict()
            for name, vals in cgaps.items() if vals]
    return out
