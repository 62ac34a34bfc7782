"""The typed routing currency: :class:`RoutingPlan` + :class:`RoutingOperand`.

PR-5 made the pair→port assignment a swappable traced operand of the jitted
engine, but the operand itself stayed a bare padded ``(M, P)`` one-hot
matrix (or its ``(P,)`` index twin) that every caller built, validated and
argmax'd by hand. Multi-hop paths and multicast forwarding trees don't fit
a one-hot column — a demand row may now occupy *several* ports — so the
routing currency becomes typed:

* :class:`RoutingPlan` — the host-side description: one ordered port tuple
  per demand row (a 1-hop unicast row is ``(m,)``, a relay path is
  ``(m1, m2, ...)``, a multicast tree is the ordered tuple of its distinct
  forwarding edges), the padded leg bound, which rows are trees, and
  provenance. This is what planners return and every public API accepts.
* :class:`RoutingOperand` — the device-side *leg list* the engine
  aggregates with: each leg is one (row, port) attachment with a VPN
  counterfactual share and an attachment weight, padded to ``n_legs`` with
  zero-weight legs. The ``primary`` field keeps the (P,) first-hop index
  array every per-pair consumer (observability ring, ``modes()``, sync
  groups) already understands.

Degeneration contract (property-tested): a plan whose rows are all 1-hop
produces legs in ascending row order with unit weights, so the engine's
``segment_sum`` aggregation is **bit-for-bit** the pre-plan one-hot path —
gathering with identity indices and multiplying by 1.0 are IEEE-exact, and
padding legs contribute exact ``+0.0`` to non-negative cost sums.

Legacy bare-array routings (``(P,)`` port indices or ``(M, P)`` one-hot
matrices) are accepted everywhere through :func:`as_routing_plan`, which
raises a :class:`DeprecationWarning` naming the call site — the same
one-release shim pattern as the ``repro.fleet`` facade.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import NamedTuple, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

__all__ = [
    "RoutingOperand",
    "RoutingPlan",
    "as_routing_plan",
    "padded_operand_np",
]


class RoutingOperand(NamedTuple):
    """Device-side leg list — the traceable pytree the engine aggregates.

    ``E = n_legs`` is the padded leg bound; swapping any plan padded to the
    same ``E`` (whatever its hop depth or tree shape) reuses the compiled
    program. Padding legs have ``attach_w == vpn_w == 0`` and point at
    row/port 0 (or the pool's inert pad row/port), so they add exact zeros.
    """

    leg_pair: jax.Array   # (E,) int32 demand-row index of each leg
    leg_port: jax.Array   # (E,) int32 port index of each leg
    vpn_w: jax.Array      # (E,) float VPN-counterfactual share (1/n_hops)
    attach_w: jax.Array   # (E,) float 1.0 active leg / 0.0 padding
    primary: jax.Array    # (P,) int32 first-hop port per demand row

    @property
    def n_legs(self) -> int:
        return self.leg_pair.shape[-1]

    @property
    def n_rows(self) -> int:
        return self.primary.shape[-1]


@dataclasses.dataclass(frozen=True)
class RoutingPlan:
    """One routing decision for a topology: a port path per demand row.

    ``paths[i]`` is the ordered tuple of DISTINCT ports demand row ``i``
    occupies — ``(m,)`` for classic unicast, ``(m1, m2)`` for a relay path
    (pricing, capacity headroom and the toggle FSM's window costs compose
    per hop), or a multicast forwarding tree's edge set (shared edges
    appear once and are charged once). ``n_legs`` is the padded leg bound
    of the device operand: plans padded to the same bound swap into a
    running stream or pooled gateway slot with zero recompiles.
    """

    paths: Tuple[Tuple[int, ...], ...]
    n_ports: int
    n_legs: int = -1                    # -1 -> tight bound (total_hops)
    tree_rows: Tuple[int, ...] = ()     # row indices that are multicast trees
    provenance: str = "manual"

    def __post_init__(self) -> None:
        paths = tuple(tuple(int(m) for m in p) for p in self.paths)
        object.__setattr__(self, "paths", paths)
        assert len(paths) >= 1, "a RoutingPlan needs at least one row"
        for i, path in enumerate(paths):
            assert len(path) >= 1, f"row {i}: empty port path"
            assert len(set(path)) == len(path), (
                f"row {i}: path {path} visits a port twice"
            )
            assert all(0 <= m < self.n_ports for m in path), (
                f"row {i}: port out of range [0, {self.n_ports}) in {path}"
            )
        tr = tuple(sorted(int(i) for i in self.tree_rows))
        assert all(0 <= i < len(paths) for i in tr), "tree_rows out of range"
        object.__setattr__(self, "tree_rows", tr)
        tight = sum(len(p) for p in paths)
        n_legs = tight if self.n_legs < 0 else int(self.n_legs)
        assert n_legs >= tight, (
            f"n_legs={n_legs} cannot hold {tight} routed legs — pad_to() a "
            "larger bound"
        )
        object.__setattr__(self, "n_legs", n_legs)

    # -- shape ------------------------------------------------------------
    @property
    def n_rows(self) -> int:
        return len(self.paths)

    @property
    def hop_depth(self) -> int:
        """Longest path (1 for a pure unicast plan)."""
        return max(len(p) for p in self.paths)

    @property
    def total_hops(self) -> int:
        return sum(len(p) for p in self.paths)

    @property
    def is_unicast(self) -> bool:
        """True when every row is a classic 1-hop unicast assignment."""
        return self.hop_depth == 1 and not self.tree_rows

    # -- views ------------------------------------------------------------
    @property
    def primary(self) -> np.ndarray:
        """(P,) first-hop port per row — the legacy ``routing_idx`` view."""
        return np.array([p[0] for p in self.paths], dtype=np.int64)

    def port_indices(self) -> np.ndarray:
        """(P,) port indices — only defined for pure 1-hop unicast plans."""
        if not self.is_unicast:
            raise TypeError(
                "port_indices() is only defined for 1-hop unicast plans; "
                f"this plan has hop_depth={self.hop_depth}, "
                f"{len(self.tree_rows)} tree rows — use .paths"
            )
        return self.primary

    def __array__(self, dtype=None, copy=None):
        a = self.port_indices()
        return a.astype(dtype) if dtype is not None else a

    def ports_used(self) -> Tuple[int, ...]:
        return tuple(sorted({m for p in self.paths for m in p}))

    @property
    def matrix(self) -> np.ndarray:
        """(M, P) float64 multi-hot membership matrix (one-hot when every
        row is 1-hop — exactly the legacy routing matrix)."""
        R = np.zeros((self.n_ports, self.n_rows))
        for i, path in enumerate(self.paths):
            R[list(path), i] = 1.0
        return R

    # -- derivation -------------------------------------------------------
    def pad_to(self, n_legs: int) -> "RoutingPlan":
        """Same plan under a larger padded leg bound (zero-weight legs)."""
        return dataclasses.replace(self, n_legs=int(n_legs))

    def replace_path(
        self, row: int, path: Union[int, Sequence[int]]
    ) -> "RoutingPlan":
        """A new plan with row ``row`` re-routed (int means 1-hop)."""
        p = (int(path),) if isinstance(path, (int, np.integer)) else tuple(path)
        paths = list(self.paths)
        paths[int(row)] = p
        tight = sum(len(q) for q in paths)
        return dataclasses.replace(
            self, paths=tuple(paths), n_legs=max(self.n_legs, tight)
        )

    def operand(self, dtype=None) -> RoutingOperand:
        """Stack to the device leg list, padded to ``n_legs``."""
        f = dtype or jnp.result_type(float)
        lp = np.zeros(self.n_legs, np.int32)
        lm = np.zeros(self.n_legs, np.int32)
        vw = np.zeros(self.n_legs, np.float64)
        aw = np.zeros(self.n_legs, np.float64)
        k = 0
        for i, path in enumerate(self.paths):
            w = 1.0 / len(path)
            for m in path:
                lp[k], lm[k], vw[k], aw[k] = i, m, w, 1.0
                k += 1
        return RoutingOperand(
            leg_pair=jnp.asarray(lp, jnp.int32),
            leg_port=jnp.asarray(lm, jnp.int32),
            vpn_w=jnp.asarray(vw, f),
            attach_w=jnp.asarray(aw, f),
            primary=jnp.asarray(self.primary, jnp.int32),
        )

    # -- constructors ------------------------------------------------------
    @classmethod
    def from_indices(
        cls,
        indices: Sequence[int],
        n_ports: int,
        *,
        n_legs: int = -1,
        provenance: str = "from_indices",
    ) -> "RoutingPlan":
        r = np.asarray(indices, dtype=np.int64)
        assert r.ndim == 1, f"expected (P,) port indices, got shape {r.shape}"
        return cls(
            paths=tuple((int(m),) for m in r),
            n_ports=int(n_ports),
            n_legs=n_legs,
            provenance=provenance,
        )

    @classmethod
    def from_matrix(
        cls, matrix, *, n_legs: int = -1, provenance: str = "from_matrix"
    ) -> "RoutingPlan":
        """From a padded one-hot ``(M, P)`` matrix (the legacy operand)."""
        R = np.asarray(matrix, dtype=np.float64)
        assert R.ndim == 2, f"expected (M, P) matrix, got shape {R.shape}"
        colsum = R.sum(axis=0)
        assert np.all(colsum == 1.0) and np.all((R == 0.0) | (R == 1.0)), (
            "routing matrix must be one-hot per pair column"
        )
        return cls.from_indices(
            np.argmax(R, axis=0), R.shape[0], n_legs=n_legs,
            provenance=provenance,
        )

    @classmethod
    def from_operand(
        cls,
        op: RoutingOperand,
        n_ports: int,
        *,
        tree_rows: Sequence[int] = (),
        provenance: str = "from_operand",
    ) -> "RoutingPlan":
        lp = np.asarray(op.leg_pair)
        lm = np.asarray(op.leg_port)
        aw = np.asarray(op.attach_w)
        P = int(np.asarray(op.primary).shape[0])
        paths: list = [[] for _ in range(P)]
        for i, m, w in zip(lp, lm, aw):
            if w != 0.0:
                paths[int(i)].append(int(m))
        return cls(
            paths=tuple(tuple(p) for p in paths),
            n_ports=int(n_ports),
            n_legs=int(lp.shape[0]),
            tree_rows=tuple(tree_rows),
            provenance=provenance,
        )


def as_routing_plan(
    routing,
    *,
    n_ports: int,
    context: str = "this API",
    n_legs: int = -1,
) -> RoutingPlan:
    """Normalize any accepted routing form to a :class:`RoutingPlan`.

    ``RoutingPlan`` passes through untouched. The legacy bare-array forms —
    a ``(P,)`` port-index sequence or a padded one-hot ``(M, P)`` matrix —
    keep working for one release but raise a :class:`DeprecationWarning`
    naming the call site, mirroring the ``repro.fleet`` facade shims.
    """
    if isinstance(routing, RoutingPlan):
        return routing
    r = np.asarray(routing)
    if r.ndim == 1:
        warnings.warn(
            f"passing bare (P,) routing indices to {context} is deprecated; "
            "pass a RoutingPlan (e.g. RoutingPlan.from_indices(r, n_ports) "
            "or the plan returned by optimize_routing)",
            DeprecationWarning,
            stacklevel=3,
        )
        return RoutingPlan.from_indices(
            r, n_ports, n_legs=n_legs, provenance=f"legacy-indices:{context}"
        )
    if r.ndim == 2:
        warnings.warn(
            f"passing a bare (M, P) one-hot routing matrix to {context} is "
            "deprecated; pass a RoutingPlan (RoutingPlan.from_matrix(R))",
            DeprecationWarning,
            stacklevel=3,
        )
        return RoutingPlan.from_matrix(
            r, n_legs=n_legs, provenance=f"legacy-matrix:{context}"
        )
    raise TypeError(
        f"{context}: cannot interpret routing of type {type(routing).__name__} "
        f"with shape {getattr(r, 'shape', None)} as a RoutingPlan"
    )


def padded_operand_np(
    plan: RoutingPlan,
    *,
    n_legs: int,
    n_rows: int,
    pad_pair: int,
    pad_port: int,
) -> RoutingOperand:
    """Host-side padded operand for the pooled gateway: legs padded to
    ``n_legs`` pointing at the pool's inert (pad_pair, pad_port) slot with
    zero weights, primary padded to ``n_rows`` with ``pad_port``.

    Returns a :class:`RoutingOperand` of NUMPY fields (the pool tiles and
    uploads them itself under ``jax.enable_x64``).
    """
    tight = plan.total_hops
    assert n_legs >= tight, f"legs_cap {n_legs} < {tight} routed legs"
    assert n_rows >= plan.n_rows
    lp = np.full(n_legs, pad_pair, np.int32)
    lm = np.full(n_legs, pad_port, np.int32)
    vw = np.zeros(n_legs, np.float64)
    aw = np.zeros(n_legs, np.float64)
    k = 0
    for i, path in enumerate(plan.paths):
        w = 1.0 / len(path)
        for m in path:
            lp[k], lm[k], vw[k], aw[k] = i, m, w, 1.0
            k += 1
    primary = np.full(n_rows, pad_port, np.int32)
    primary[: plan.n_rows] = plan.primary
    return RoutingOperand(
        leg_pair=lp, leg_port=lm, vpn_w=vw, attach_w=aw, primary=primary
    )
