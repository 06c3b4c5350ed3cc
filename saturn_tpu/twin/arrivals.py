"""Seeded Poisson + diurnal-burst arrival synthesis.

One generator, so that the twin and a live service driven over the same
trace (``tests/test_twin.py::TestRealServiceFidelity``) draw the *same*
arrivals from the same seed and can never drift. The generator consumes its
RNG in a fixed order — one ``expovariate`` gap, then one ``randint``
priority, per arrival — so a seed reproduces its trace draw for draw.

Traffic shape: a Poisson base rate modulated by periodic diurnal bursts —
every ``burst_every`` arrivals, a window of ``burst_len`` arrivals comes in
at ``burst_rate_hz`` instead of ``base_rate_hz`` (the arrival pattern a
serving front door actually sees). Scaling the rates up by orders of
magnitude (the twin's "million-user" campaigns) preserves the shape.
"""

from __future__ import annotations

import random
from dataclasses import dataclass
from typing import List, Mapping, Optional

#: Diurnal-burst cycle defaults.
BURST_EVERY = 50          # every 50 arrivals, a burst window opens...
BURST_LEN = 20            # ...for 20 arrivals


@dataclass(frozen=True)
class Arrival:
    """One synthesized arrival on the stream's own time axis."""

    index: int
    at_s: float       # offset from stream start (cumulative gaps)
    gap_s: float      # the inter-arrival gap drawn for this arrival
    priority: float   # integer-valued priority class, 0.0 .. 2.0
    in_burst: bool    # whether this arrival fell inside a burst window
    tenant: Optional[str] = None  # owning tenant (tenant_mix runs only)


def arrival_stream(n_jobs: int, *,
                   base_rate_hz: float,
                   burst_rate_hz: float,
                   burst_every: int = BURST_EVERY,
                   burst_len: int = BURST_LEN,
                   seed: int = 0,
                   tenant_mix: Optional[Mapping[str, float]] = None,
                   ) -> List[Arrival]:
    """Synthesize a deterministic arrival trace.

    Same ``(n_jobs, rates, cycle, seed)`` → the identical list, on every
    platform CPython's Mersenne Twister runs on. Raises on nonsensical
    rates rather than emitting an empty or divergent stream.

    ``tenant_mix`` maps tenant name → positive arrival weight: each
    arrival is tagged with a tenant drawn from the mix (a 10:1 weight
    skew yields the noisy-neighbour traffic the fairness tests need).
    Tenant draws come from a *separate* RNG stream seeded as
    ``f"{seed}:tenant"`` so the primary gap/priority draw order — one
    ``expovariate`` plus one ``randint`` per arrival — is untouched:
    adding tenants to a historical seed reproduces the historical trace
    draw for draw, just tagged.
    """
    if n_jobs < 0:
        raise ValueError(f"n_jobs must be >= 0, got {n_jobs}")
    if base_rate_hz <= 0 or burst_rate_hz <= 0:
        raise ValueError(
            f"arrival rates must be positive, got base={base_rate_hz} "
            f"burst={burst_rate_hz}"
        )
    if burst_every <= 0 or burst_len < 0:
        raise ValueError(
            f"burst cycle must satisfy burst_every > 0 and burst_len >= 0, "
            f"got every={burst_every} len={burst_len}"
        )
    tenants = None
    weights = None
    tenant_rng = None
    if tenant_mix:
        if any(w <= 0 for w in tenant_mix.values()):
            raise ValueError(
                f"tenant_mix weights must be positive, got {tenant_mix}"
            )
        tenants = list(tenant_mix)
        weights = [float(tenant_mix[t]) for t in tenants]
        tenant_rng = random.Random(f"{seed}:tenant")
    rng = random.Random(seed)
    out: List[Arrival] = []
    t = 0.0
    for i in range(n_jobs):
        in_burst = (i % burst_every) < burst_len
        rate = burst_rate_hz if in_burst else base_rate_hz
        gap = rng.expovariate(rate)
        priority = float(rng.randint(0, 2))
        tenant = (tenant_rng.choices(tenants, weights=weights)[0]
                  if tenant_rng is not None else None)
        t += gap
        out.append(Arrival(index=i, at_s=t, gap_s=gap,
                           priority=priority, in_burst=in_burst,
                           tenant=tenant))
    return out
