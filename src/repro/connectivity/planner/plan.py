"""The resolved execution plan every kernel-backed solve runs under.

:class:`ExecutionPlan` is the first-class replacement for the old frozen
``KernelPlan`` + heuristic tables that lived inside
``kernels.contour_mm.ops``.  One plan answers every dispatch question a
solve path has to settle before tracing:

* which **backend** realises the MM sweep (``"xla"`` scatter-min, the
  scalar ``"pallas"`` kernel, or the label-blocked ``"pallas_blocked"``
  kernel — same names as ``ops.BACKENDS``);
* the **tile sizes** of that backend (``block_edges`` / ``label_block`` /
  ``chunk_updates``) and whether Pallas runs in ``interpret`` mode;
* how the work-adaptive frontier is **realised physically**:
  ``compact_schedule="masked"`` keeps the single in-jit ``lax.while_loop``
  with full-shape masked tiles (the only legal choice under an enclosing
  trace — ``vmap``/``shard_map``/user jit), ``"staged"`` re-enters the
  loop at physically sliced, power-of-two-bucketed edge shapes so the
  launched grid actually shrinks with the frontier
  (``planner.staged``, DESIGN.md §14);
* whether the single-tile **fused relabel + scatter-min** Pallas pass is
  eligible (``fuse_relabel`` — ``blocked.fused_relax_pallas``);
* where the plan **came from** (``origin``): ``"heuristic"`` cold-start
  tables, ``"tuned"`` from the measuring autotuner's on-disk cache,
  ``"pinned"`` by the caller, or ``"fallback"`` after a kernel-launch
  failure demoted the bucket (with an expiry, so XLA is retuned rather
  than pinned forever).  ``origin`` is provenance, not semantics: two
  plans equal up to origin trace to identical programs.

The dataclass is frozen and hashable so it can ride through every jitted
entry point as a static argument, exactly like ``KernelPlan`` did.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

BACKENDS = ("auto", "xla", "pallas", "pallas_blocked")
COMPACT_SCHEDULES = ("masked", "staged")
ORIGINS = ("heuristic", "tuned", "pinned", "fallback")

# Cache / bucket keys use power-of-two size buckets: plans generalise
# across graphs of similar scale, and the jit cache cannot be fragmented
# by one entry per exact (n, m).
_CONFIG_FIELDS = ("backend", "block_edges", "label_block", "chunk_updates",
                  "interpret", "compact_schedule", "fuse_relabel",
                  "chunk_bucket")


def next_pow2(x: int) -> int:
    """Smallest power of two >= max(x, 1)."""
    return 1 << max(int(x) - 1, 0).bit_length()


def size_bucket(x: int) -> int:
    """The pow2 bucket a vertex/edge count falls in (for plan keys)."""
    return next_pow2(max(int(x), 1))


def plan_key(platform: str, n_vertices: int, m_edges: int) -> str:
    """Tuning-cache key: (platform, n-bucket, m-bucket)."""
    return f"{platform}/n{size_bucket(n_vertices)}/m{size_bucket(m_edges)}"


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Resolved backend + tile sizes + schedule for one solve (static)."""

    backend: str                    # concrete: "xla"|"pallas"|"pallas_blocked"
    block_edges: int = 512          # edge block of the scalar pallas kernel
    label_block: int = 1024         # L tile height of the blocked kernel
    chunk_updates: int = 1024       # update-stream chunk of the blocked kernel
    interpret: bool = False         # Pallas interpreter mode (CPU validation)
    compact_schedule: str = "masked"  # frontier realisation: masked | staged
    fuse_relabel: bool = False      # single-tile fused gather+scatter-min pass
    chunk_bucket: int = 0           # out-of-core pow2 edge chunk (0 = n/a)
    origin: str = "heuristic"       # heuristic | tuned | pinned | fallback

    def validate(self) -> "ExecutionPlan":
        if self.backend not in BACKENDS[1:]:
            raise ValueError(
                f"ExecutionPlan.backend must be concrete, one of "
                f"{BACKENDS[1:]}; got {self.backend!r}")
        if self.compact_schedule not in COMPACT_SCHEDULES:
            raise ValueError(
                f"compact_schedule {self.compact_schedule!r} not one of "
                f"{COMPACT_SCHEDULES}")
        if self.origin not in ORIGINS:
            raise ValueError(f"origin {self.origin!r} not one of {ORIGINS}")
        for f in ("block_edges", "label_block", "chunk_updates"):
            v = getattr(self, f)
            if not isinstance(v, int) or v < 1:
                raise ValueError(f"{f} must be a positive int, got {v!r}")
        cb = self.chunk_bucket
        if not isinstance(cb, int) or cb < 0 or (cb and cb & (cb - 1)):
            raise ValueError(
                f"chunk_bucket must be 0 or a power of two, got {cb!r}")
        return self

    def replace(self, **updates) -> "ExecutionPlan":
        return dataclasses.replace(self, **updates)

    # -- serialisation (tuning cache / bench artifact) --------------------
    def to_config(self) -> dict:
        """JSON-safe config dict (origin excluded — it is per-resolution)."""
        return {f: getattr(self, f) for f in _CONFIG_FIELDS}

    @classmethod
    def from_config(cls, config: dict, origin: str = "tuned"
                    ) -> "ExecutionPlan":
        """Rebuild a plan from :meth:`to_config` output; raises on any
        unknown/malformed field (the cache layer turns that into a
        heuristic fallback)."""
        if not isinstance(config, dict):
            raise ValueError(f"plan config must be a dict, got "
                             f"{type(config).__name__}")
        unknown = set(config) - set(_CONFIG_FIELDS)
        if unknown:
            raise ValueError(f"unknown plan config fields {sorted(unknown)}")
        kwargs = dict(config)
        for f in ("interpret", "fuse_relabel"):
            if f in kwargs and not isinstance(kwargs[f], bool):
                raise ValueError(f"{f} must be a bool")
        return cls(origin=origin, **kwargs).validate()

    def config_equal(self, other: Optional["ExecutionPlan"]) -> bool:
        """True when the two plans trace to the same program (origin and
        provenance aside)."""
        return other is not None and self.to_config() == other.to_config()

    def provenance_entry(self) -> str:
        """The ``plan:`` line recorded in ``ComponentResult.provenance``."""
        oc = f" chunk={self.chunk_bucket}" if self.chunk_bucket else ""
        return (f"plan:{self.backend} origin={self.origin} "
                f"schedule={self.compact_schedule} "
                f"lb={self.label_block} cu={self.chunk_updates} "
                f"be={self.block_edges} fused={int(self.fuse_relabel)} "
                f"interpret={int(self.interpret)}{oc}")

    @classmethod
    def from_kernel_plan(cls, plan, origin: str = "pinned"
                         ) -> "ExecutionPlan":
        """Lift a legacy ``KernelPlan`` (or any duck-typed plan) into an
        :class:`ExecutionPlan`; an ExecutionPlan passes through with its
        origin re-stamped only if it has none."""
        if isinstance(plan, cls):
            return plan
        return cls(
            backend=plan.backend,
            block_edges=int(plan.block_edges),
            label_block=int(plan.label_block),
            chunk_updates=int(plan.chunk_updates),
            interpret=bool(plan.interpret),
            compact_schedule=getattr(plan, "compact_schedule", "masked"),
            fuse_relabel=bool(getattr(plan, "fuse_relabel", False)),
            origin=origin,
        ).validate()
