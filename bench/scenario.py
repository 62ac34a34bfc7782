"""The benchmark's own scenario generator: link prices and demand from a seed.

A copy, kept with the benchmark so that no change to the program can move
the yardstick, of

* the price catalogs and ``make_scenario`` of ``src/repro/core/pricing.py``
  (July-2025 list prices of GCP, AWS and Azure interconnect, VPN and egress);
* the link sampling and per-link demand scaling of
  ``src/repro/fleet/scenario.py`` (``build_fleet_scenario``);
* the capacity ceilings of ``src/repro/traffic/linksim.py``;
* the four demand-trace families of the paper's section VII
  (``src/repro/traffic/{traces,mirage,puffer}.py``): constant, bursty,
  MIRAGE-like mobile users and Puffer-like live video.

Each trace family draws in its original's order, so that from the same
generator state it gives the original's columns
(``bench/tests/test_scenario.py``); loops over hours are linear filters.

Everything is plain numpy and depends only on ``seed``: the same seed gives
the same links and demand on any machine.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Sequence

import numpy as np
from scipy.signal import lfilter

INF = math.inf
TIER_PAD = 1e30           # stands in for an unbounded top tier

# --- price catalogs (src/repro/core/pricing.py) ---------------------------
AWS_EGRESS_INTERNET = ((10_240.0, 51_200.0, 153_600.0, INF), (0.09, 0.085, 0.07, 0.05))
GCP_EGRESS_PREMIUM = ((1_024.0, 10_240.0, INF), (0.12, 0.11, 0.08))
GCP_EGRESS_STANDARD = ((10_240.0, 153_600.0, INF), (0.085, 0.065, 0.045))
AZURE_EGRESS_INTERNET = ((10_240.0, 51_200.0, 153_600.0, INF), (0.087, 0.083, 0.07, 0.05))
GCP_CCI_EGRESS_INTRA_CONTINENT = 0.02
GCP_CCI_EGRESS_INTER_CONTINENT = 0.05
AWS_DX_EGRESS = 0.02
AZURE_ER_EGRESS = 0.025
GCP_CCI_PORT_10G_HR = 2.30
AWS_DX_PORT_10G_HR = 2.25
AZURE_ER_PORT_10G_HR = 2.74
GCP_VLAN_HR = {1: 0.10, 2: 0.16, 5: 0.26, 10: 0.42}
AWS_VIF_HR = 0.0
AZURE_VLAN_HR = {1: 0.12, 2: 0.18, 5: 0.30, 10: 0.46}
VPN_LEASE_HR = {"gcp": 0.055, "aws": 0.05, "azure": 0.19}

# --- capacity ceilings (src/repro/traffic/linksim.py) ---------------------
GB_PER_GBPS_HOUR = 450.0
CCI_NOMINAL_GBPS = 10.0
CCI_OVERHEAD = 0.05
VLAN_BURST_FACTOR = 1.7

CLOUD_PAIRS = (("gcp", "aws"), ("aws", "gcp"), ("gcp", "azure"), ("azure", "gcp"))
VLAN_CHOICES = (1, 2, 5, 10)


@dataclasses.dataclass(frozen=True)
class Link:
    """One link's prices and ToggleCCI operating point, as plain numbers."""

    name: str
    family: str
    L_cci: float
    V_cci: float
    c_cci: float
    L_vpn: float
    tier_bounds: tuple      # upper cumulative-volume bound per tier, last inf
    tier_rates: tuple       # marginal $/GB per tier
    D: int
    T_cci: int
    h: int
    theta1: float
    theta2: float
    capacity: float         # GB/hour


@dataclasses.dataclass(frozen=True)
class Fleet:
    """Links plus their (N, T) hourly demand in GB."""

    links: tuple
    demand: np.ndarray
    hours_per_month: int


class LinkArrays:
    """Struct-of-arrays view of a link list, the form the reference takes.

    Tier tables are padded to the deepest one with ``(TIER_PAD, 0)`` rows,
    which bill nothing.
    """

    def __init__(self, links: Sequence[Link], hours_per_month: int):
        f = lambda k: np.array([getattr(l, k) for l in links], np.float64)
        i = lambda k: np.array([getattr(l, k) for l in links], np.int64)
        self.L_cci, self.V_cci, self.c_cci, self.L_vpn = (
            f("L_cci"), f("V_cci"), f("c_cci"), f("L_vpn"))
        self.theta1, self.theta2, self.capacity = f("theta1"), f("theta2"), f("capacity")
        self.D, self.T_cci, self.h = i("D"), i("T_cci"), i("h")
        kt = max(len(l.tier_bounds) for l in links)
        self.tier_bounds = np.full((len(links), kt), TIER_PAD)
        self.tier_rates = np.zeros((len(links), kt))
        for r, l in enumerate(links):
            n = len(l.tier_bounds)
            self.tier_bounds[r, :n] = [b if math.isfinite(b) else TIER_PAD
                                       for b in l.tier_bounds]
            self.tier_rates[r, :n] = l.tier_rates
        self.hours_per_month = int(hours_per_month)
        self.n = len(links)


def _rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one named stream of one seed (any whole number)."""
    return np.random.default_rng([int(seed) % 2**64, *stream])


def make_link(name, family, src, dst, *, intercontinental, colocation_far,
              vlan_gbps, gcp_tier, D, T_cci, h, theta1, theta2) -> Link:
    """``make_scenario`` of ``src/repro/core/pricing.py`` plus the link's
    capacity ceiling (the VLAN's elastic burst, capped by the CCI port)."""
    assert "gcp" in (src, dst) and src != dst
    other = dst if src == "gcp" else src
    L_cci = GCP_CCI_PORT_10G_HR + {"aws": AWS_DX_PORT_10G_HR,
                                   "azure": AZURE_ER_PORT_10G_HR}[other]
    V_cci = GCP_VLAN_HR[vlan_gbps] + {"aws": AWS_VIF_HR,
                                      "azure": AZURE_VLAN_HR[vlan_gbps]}[other]
    far = intercontinental or colocation_far
    if src == "gcp":
        c_cci = GCP_CCI_EGRESS_INTER_CONTINENT if far else GCP_CCI_EGRESS_INTRA_CONTINENT
    else:
        c_cci = {"aws": AWS_DX_EGRESS, "azure": AZURE_ER_EGRESS}[src] + (0.02 if far else 0.0)
    L_vpn = VPN_LEASE_HR[src] + VPN_LEASE_HR[dst]
    bounds, rates = {
        "gcp": GCP_EGRESS_PREMIUM if gcp_tier == "premium" else GCP_EGRESS_STANDARD,
        "aws": AWS_EGRESS_INTERNET,
        "azure": AZURE_EGRESS_INTERNET,
    }[src]
    if intercontinental:
        rates = tuple(r + 0.03 for r in rates)
    cap = min(vlan_gbps * VLAN_BURST_FACTOR,
              CCI_NOMINAL_GBPS * (1.0 - CCI_OVERHEAD)) * GB_PER_GBPS_HOUR
    return Link(name=name, family=family, L_cci=L_cci, V_cci=V_cci, c_cci=c_cci,
                L_vpn=L_vpn, tier_bounds=tuple(bounds), tier_rates=tuple(rates),
                D=int(D), T_cci=int(T_cci), h=int(h), theta1=float(theta1),
                theta2=float(theta2), capacity=float(cap))


def _tier_cost_from_zero(month_gb: np.ndarray, a: LinkArrays) -> np.ndarray:
    """Cost of a month's volume billed from an empty month, per link."""
    prev = np.concatenate([np.zeros((a.n, 1)), a.tier_bounds[:, :-1]], axis=1)
    seg = np.clip(np.minimum(month_gb[:, None], a.tier_bounds) - prev, 0.0, None)
    return (seg * a.tier_rates).sum(axis=1)


def breakeven_rate(a: LinkArrays) -> np.ndarray:
    """Constant GB/hour at which a link's steady hourly VPN and CCI costs
    are equal (``breakeven_rate_gb_per_hour``, one bisection for all links)."""
    lo, hi = np.zeros(a.n), np.full(a.n, 1e9)
    top = a.tier_rates[:, 0]
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        month_gb = mid * a.hours_per_month
        with np.errstate(invalid="ignore", divide="ignore"):
            rate = np.where(month_gb > 0,
                            _tier_cost_from_zero(month_gb, a) / month_gb, top)
        vpn_hr = a.L_vpn + rate * mid
        cci_hr = a.L_cci + a.V_cci + a.c_cci * mid
        up = cci_hr > vpn_hr
        lo, hi = np.where(up, mid, lo), np.where(up, hi, mid)
    return 0.5 * (lo + hi)


# --- demand-trace families (paper section VII) ----------------------------

_MIRAGE_DIURNAL = np.array(
    [0.2, 0.1, 0.1, 0.1, 0.1, 0.2, 0.5, 0.9, 1.2, 1.3, 1.3, 1.4,
     1.5, 1.4, 1.3, 1.3, 1.4, 1.6, 1.9, 2.1, 2.0, 1.6, 1.0, 0.5])
_MIRAGE_DIURNAL = _MIRAGE_DIURNAL / _MIRAGE_DIURNAL.sum()
_PUFFER_DIURNAL = np.array(
    [0.25, 0.15, 0.10, 0.08, 0.08, 0.10, 0.18, 0.30, 0.40, 0.45, 0.50, 0.55,
     0.60, 0.60, 0.58, 0.60, 0.65, 0.75, 0.90, 1.00, 0.95, 0.80, 0.60, 0.40])
_PUFFER_WEEKLY = np.array([0.92, 0.94, 0.95, 0.97, 1.05, 1.15, 1.10])


def constant_columns(n: int, hours: int, rng) -> np.ndarray:
    return np.ones((hours, n))


def bursty_columns(n: int, hours: int, rng) -> np.ndarray:
    """Poisson bursts (one a month), ~1 week long, ~400 GB/hour, with 5%
    hourly jitter; bursts superpose (``traffic/traces.bursty_trace``)."""
    out = np.zeros((hours, n))
    for c in range(n):
        t = 0.0
        while True:
            t += rng.exponential(730.0)
            start = int(t)
            if start >= hours:
                break
            dur = max(1, int(round(rng.normal(168.0, 42.0))))
            stop = min(hours, start + dur)
            level = max(0.0, rng.normal(400.0, 100.0))
            jitter = rng.normal(1.0, 0.05, size=stop - start).clip(0.5, 1.5)
            out[start:stop, c] += level * jitter
    return out


def mirage_columns(n: int, hours: int, rng, n_devices: int = 280,
                   users_per_pair: int = 2000, activity_sigma: float = 1.5,
                   activity_corr_days: float = 60.0) -> np.ndarray:
    """MIRAGE-like mobile traffic (``traffic/mirage.mirage_trace``): a pool
    of daily device profiles; every day each user adopts one, drawn
    uniformly (one multinomial per pair and day); a multi-week AR(1)
    campaign envelope (sigma 1.5, 60-day correlation) over all users.

    Draws in the original's order, so that from the same generator state it
    gives the original's columns (``bench/tests/test_scenario.py``)."""
    days = math.ceil(hours / 24)
    activity = rng.lognormal(-1.5, 1.2, size=n_devices)
    pool = np.zeros((n_devices, 24))
    for i in range(n_devices):
        k = rng.poisson(6)
        if k:
            hrs = rng.choice(24, size=k, p=_MIRAGE_DIURNAL)
            np.add.at(pool[i], hrs, rng.lognormal(-3.0, 1.4, size=k) * activity[i])
    users = np.bincount(rng.integers(n, size=users_per_pair * n), minlength=n)
    rho = math.exp(-1.0 / activity_corr_days)
    sig = activity_sigma * math.sqrt(1 - rho**2)
    g = lfilter([1.0], [1.0, -rho], rng.normal(0.0, sig, size=days))
    env = np.exp(g - 0.5 * activity_sigma**2)
    uniform = np.full(n_devices, 1.0 / n_devices)
    out = np.empty((days * 24, n))
    for day in range(days):
        counts = rng.multinomial(users, uniform)                 # (n, n_devices)
        out[day * 24:(day + 1) * 24] = env[day] * (counts @ pool).T
    return out[:hours]


def puffer_columns(n: int, hours: int, rng) -> np.ndarray:
    """Puffer-like live video (``traffic/puffer.puffer_trace``): diurnal and
    weekly viewer envelopes, a Zipf-ish channel popularity and a slow AR(1)
    log-modulation, at 2.7 GB per viewer-hour.

    Draws in the original's order (channel by channel) and multiplies in its
    order, so that from the same generator state it gives the original's
    columns; the AR(1) loop is one linear filter."""
    T = math.ceil(hours / 24) * 24
    t = np.arange(T)
    pop = (1.0 / (1.0 + np.arange(n))) ** 0.7
    eps = rng.normal(0.0, 0.05, size=(n, T))
    eps[:, 0] = 0.0
    mod = lfilter([1.0], [1.0, -0.98], eps, axis=1)
    viewers = (200.0 * pop[:, None] * _PUFFER_DIURNAL[t % 24]
               * _PUFFER_WEEKLY[(t // 24) % 7] * np.exp(mod))
    return (viewers * 2.7).T[:hours]


FAMILY_COLUMNS = {
    "constant": constant_columns,
    "bursty": bursty_columns,
    "mirage": mirage_columns,
    "puffer": puffer_columns,
}


def build_fleet(seed: int, *, n_links: int, horizon: int, families: Sequence[str],
                hours_per_month: int, demand_scale: float,
                name_prefix: str = "") -> Fleet:
    """``build_fleet_scenario``: links take families in turn; each link's
    prices are sampled, and its demand column is rescaled to a log-normal
    multiple (sigma 0.7) of its own breakeven rate."""
    rng = _rng(seed, 0)
    fam_of = [families[i % len(families)] for i in range(n_links)]
    links = []
    for i in range(n_links):
        src, dst = CLOUD_PAIRS[rng.integers(len(CLOUD_PAIRS))]
        links.append(make_link(
            f"{name_prefix}{fam_of[i]}-{i:04d}", fam_of[i], src, dst,
            intercontinental=bool(rng.random() < 0.25),
            colocation_far=bool(rng.random() < 0.2),
            vlan_gbps=int(VLAN_CHOICES[rng.integers(len(VLAN_CHOICES))]),
            gcp_tier="premium" if rng.random() < 0.7 else "standard",
            D=rng.integers(24, 97), T_cci=rng.integers(72, 337),
            h=rng.integers(72, 337), theta1=rng.uniform(0.85, 0.95),
            theta2=rng.uniform(1.05, 1.2),
        ))
    arrays = LinkArrays(links, hours_per_month)
    target = breakeven_rate(arrays) * demand_scale * rng.lognormal(0.0, 0.7, n_links)
    demand = np.empty((n_links, horizon))
    for k, fam in enumerate(families):
        rows = [i for i in range(n_links) if fam_of[i] == fam]
        if rows:
            demand[rows] = FAMILY_COLUMNS[fam](len(rows), horizon, _rng(seed, 1, k)).T
    mean = demand.mean(axis=1)
    flat = mean <= 0
    demand *= np.where(flat, 0.0, target / np.where(flat, 1.0, mean))[:, None]
    demand[flat] = target[flat][:, None]
    return Fleet(tuple(links), demand, hours_per_month)


def build(config: Dict, seed: int) -> Fleet:
    """The fleet a configuration decides."""
    return build_fleet(seed, n_links=config["n_links"], horizon=config["horizon"],
                       families=tuple(config["families"]),
                       hours_per_month=config["hours_per_month"],
                       demand_scale=config["demand_scale"])
