"""Seeded trace generator: one configuration's job catalog under one traffic mix.

A configuration (``bench/configs/<name>.json``) fixes the cluster and the job
catalog: gang-size probabilities, the duration distribution, the models and
batch sizes.  A traffic mix (``bench/traffic/<name>.json``) fixes the arrivals:
an initial backlog of ``backlog_per_gpu`` jobs per GPU that all arrive within
``backlog_window_s``, then a Poisson stream at ``offered_load`` (GPU-seconds
offered per GPU-second of capacity) for ``warmup_rounds + horizon_rounds``
rounds.

The multiset of jobs (gang, duration, model, batch) and the arrival times are
drawn once from the traffic's ``catalog_seed``, with stratified quantiles so
that every marginal matches the catalog closely, and so is the backlog's
order.  ``--seed`` only permutes which stream job takes which arrival slot.
So every seed offers the same work, the stream in another order: the
window's rounds differ from seed to seed by the stream's jobs, not by a
reshuffled backlog (which moved a 40 s window's mean ``decide()`` time by
about 10 % from seed to seed).

The arithmetic follows ``repro.core.traces.shockwave_trace`` (duration
classes) and ``repro.workloads.generators`` (Pareto durations), vectorised.
"""

from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np


def expected_gang(catalog: Dict) -> float:
    sizes = np.asarray(catalog["gangs"]["sizes"], np.float64)
    probs = np.asarray(catalog["gangs"]["probs"], np.float64)
    return float((sizes * probs).sum() / probs.sum())


def expected_duration(catalog: Dict) -> float:
    """Mean isolated runtime (s) of the catalog's duration distribution."""
    d = catalog["durations"]
    if d["kind"] == "classes":
        p = np.asarray([c[0] for c in d["classes"]], np.float64)
        mid = np.asarray([(c[1] + c[2]) / 2.0 for c in d["classes"]])
        return float((p * mid).sum() / p.sum())
    if d["kind"] == "pareto":
        # D = scale * (1 + Lomax(alpha)), clipped to [min_s, cap_s]:
        # E = lo + integral_lo^hi P(D > t) dt, P(D > t) = min(1, (t/scale)^-alpha)
        m, a = float(d["scale_s"]), float(d["alpha"])
        lo, hi = float(d["min_s"]), float(d["cap_s"])
        flat_hi = min(max(m, lo), hi)  # P(D > t) = 1 on [lo, flat_hi)
        tail = 0.0
        if hi > flat_hi:
            tail = m / (1.0 - a) * ((hi / m) ** (1.0 - a) - (flat_hi / m) ** (1.0 - a))
        return lo + (flat_hi - lo) + tail
    raise ValueError(f"unknown duration kind {d['kind']!r}")


def _durations_at(catalog: Dict, u: np.ndarray) -> np.ndarray:
    """Inverse CDF of the catalog's duration distribution at quantiles ``u``."""
    d = catalog["durations"]
    if d["kind"] == "classes":
        p = np.asarray([c[0] for c in d["classes"]], np.float64)
        cum = np.concatenate([[0.0], np.cumsum(p / p.sum())])
        k = np.clip(np.searchsorted(cum, u, side="right") - 1, 0, len(p) - 1)
        lo = np.asarray([c[1] for c in d["classes"]], np.float64)[k]
        hi = np.asarray([c[2] for c in d["classes"]], np.float64)[k]
        v = (u - cum[k]) / (cum[k + 1] - cum[k])
        return lo + v * (hi - lo)
    if d["kind"] == "pareto":
        t = float(d["scale_s"]) * (1.0 - u) ** (-1.0 / float(d["alpha"]))
        return np.clip(t, float(d["min_s"]), float(d["cap_s"]))
    raise ValueError(f"unknown duration kind {d['kind']!r}")


def _stratified_counts(probs, n: int) -> np.ndarray:
    """Counts summing to ``n`` in the given proportions (largest remainder)."""
    p = np.asarray(probs, np.float64)
    raw = p / p.sum() * n
    counts = np.floor(raw).astype(np.int64)
    rest = n - int(counts.sum())
    counts[np.argsort(-(raw - counts), kind="stable")[:rest]] += 1
    return counts


def stream_rate_per_s(config: Dict, traffic: Dict) -> float:
    """Poisson arrival rate of the stream: offered_load * GPUs / E[gang * duration]."""
    cl, cat = config["cluster"], config["jobs"]
    gpus = cl["num_nodes"] * cl["gpus_per_node"]
    return float(traffic["offered_load"]) * gpus / (expected_gang(cat) * expected_duration(cat))


def job_rows(config: Dict, traffic: Dict, seed: int) -> Dict[str, np.ndarray]:
    """The trace as column arrays, sorted by arrival: ``arrival_s``,
    ``num_gpus``, ``duration_s``, ``model`` (index into the catalog's
    models), ``batch_size`` and ``backlog`` (True for backlog jobs)."""
    cl, cat = config["cluster"], config["jobs"]
    gpus = cl["num_nodes"] * cl["gpus_per_node"]
    round_s = float(config["sim"]["round_duration_s"])
    n_backlog = int(round(traffic["backlog_per_gpu"] * gpus))
    rate = stream_rate_per_s(config, traffic)
    horizon_s = (traffic["warmup_rounds"] + traffic["horizon_rounds"]) * round_s
    n_stream = int(math.ceil(rate * horizon_s))
    n = n_backlog + n_stream

    rng = np.random.default_rng(int(traffic["catalog_seed"]))
    counts = _stratified_counts(cat["gangs"]["probs"], n)
    gangs = np.repeat(np.asarray(cat["gangs"]["sizes"], np.int64), counts)
    # durations stratified within each gang size, so that every gang size
    # sees the whole duration distribution and the offered work is exact
    u = np.concatenate([(np.arange(c) + rng.random(c)) / max(c, 1) for c in counts])
    durations = _durations_at(cat, u)
    shuffle = rng.permutation(n)
    gangs, durations = gangs[shuffle], durations[shuffle]
    models = rng.permutation(np.arange(n) % len(cat["models"]))
    batches = np.asarray(cat["batch_sizes"], np.int64)
    batch = rng.permutation(batches[np.arange(n) % len(batches)])
    arrivals = np.concatenate(
        [
            np.sort(rng.uniform(0.0, float(traffic["backlog_window_s"]), n_backlog)),
            np.cumsum(rng.exponential(1.0 / rate, n_stream)),
        ]
    )

    # the seed decides only which stream job arrives in which slot
    srng = np.random.default_rng(int(seed))
    order = np.concatenate([np.arange(n_backlog), n_backlog + srng.permutation(n_stream)])
    cols = {
        "num_gpus": gangs[order],
        "duration_s": durations[order],
        "model": models[order],
        "batch_size": batch[order],
        "arrival_s": arrivals,
        "backlog": np.arange(n) < n_backlog,
    }
    by_time = np.argsort(arrivals, kind="stable")
    return {k: v[by_time] for k, v in cols.items()}


def offered_load(config: Dict, rows: Dict[str, np.ndarray]) -> float:
    """Realised offered load of the stream part of ``rows``: GPU-seconds
    arriving per second over the stream's span, per GPU."""
    cl = config["cluster"]
    gpus = cl["num_nodes"] * cl["gpus_per_node"]
    s = ~rows["backlog"]
    work = float((rows["num_gpus"][s] * rows["duration_s"][s]).sum())
    return work / float(rows["arrival_s"][s].max()) / gpus


def job_specs(config: Dict, traffic: Dict, seed: int) -> Tuple[List, Dict[int, int]]:
    """The program's ``JobSpec`` list for this cell and seed, and the gang of
    every job id (the benchmark's own record, for the reference)."""
    from repro.core.jobs import JobSpec
    from repro.core.profiler import MODEL_CATALOG, ThroughputProfile

    rows = job_rows(config, traffic, seed)
    models = config["jobs"]["models"]
    profile = ThroughputProfile()
    # iterations = duration * isolated throughput at the job's own gang size
    rate = {
        (m, g): profile.isolated(models[m], int(g))
        for m in range(len(models))
        for g in config["jobs"]["gangs"]["sizes"]
    }
    specs = []
    for jid in range(len(rows["arrival_s"])):
        m, g = int(rows["model"][jid]), int(rows["num_gpus"][jid])
        name = models[m]
        specs.append(
            JobSpec(
                job_id=jid,
                model=name,
                num_gpus=g,
                total_iters=float(rows["duration_s"][jid]) * rate[(m, g)],
                arrival_time=float(rows["arrival_s"][jid]),
                batch_size=int(rows["batch_size"][jid]),
                packable=True,
                is_llm=MODEL_CATALOG[name].is_llm,
            )
        )
    return specs, {jid: int(g) for jid, g in enumerate(rows["num_gpus"])}
