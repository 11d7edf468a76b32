"""PlanSpec: the single source of truth for a (d, wire, k) deployment config.

The paper's tradeoff surface has three axes — redundancy d (straggler
tolerance), the wire format (compressor + its knobs, i.e. uplink bytes), and
the bucket/backend execution schedule.  Before this module those knobs were
smeared across `TrainRun` fields, `configs.common.CodingCfg`, inline
`CocoEFConfig` construction in `launch.train.build_train_setup`, and
per-benchmark plumbing.  A `PlanSpec` is ONE frozen, serializable record of a
deployment configuration; everything else derives from it:

  plan.wire(n, nd)                  -> the WireFormat actually shipped
  plan.coding_collective_config()   -> the collective config for the mesh step
  plan.rank_wire_bytes(n)           -> per-rank uplink bytes (StepTimer price)

so "the config priced is the config run" is a property of the type, not a
per-benchmark convention.  `sim.planner.plan_search` enumerates PlanSpecs and
`launch.train.TrainRun(plan=...)` executes one.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, Optional, Tuple, Union

import jax.numpy as jnp
import numpy as np

from .collectives import (CodingCollectiveConfig, DenseWire, SignWire,
                          SparseWire, WireFormat)

__all__ = ["PlanSpec", "build_wire", "PLAN_SCHEMA", "ALLOCATIONS",
           "PLAN_COMPRESSORS", "BUCKET_SCHEDULES", "PLAN_BACKENDS"]

PLAN_SCHEMA = "repro.plan/v1"
ALLOCATIONS = ("uniform", "rate_aware", "exact_load")
PLAN_COMPRESSORS = ("sign", "block_topk", "topk", "identity")
BUCKET_SCHEDULES = ("serial", "pipelined")
PLAN_BACKENDS = ("auto", "pallas", "jnp")


def build_wire(compressor: str, *, group_size: int = 512,
               k_per_block: Union[int, Tuple[int, ...]] = 8,
               block_size: int = 256, topk_k: int = 64,
               value_dtype: str = "float32", n: int = 0, nd: int = 1,
               num_buckets: int = 1) -> WireFormat:
    """Wire format for one bucket of `n` coords over `nd` all_to_all chunks.

    This is THE mapping from compressor name + knobs to a WireFormat; both
    `PlanSpec.wire` and `CocoEFConfig.wire_format` delegate here so the two
    config planes can never drift.
    """
    if compressor == "sign":
        return SignWire(group_size=group_size)
    if compressor == "block_topk":
        return SparseWire(k_per_block=k_per_block, block_size=block_size,
                          value_dtype=value_dtype)
    if compressor == "topk":
        # global top-K realized as one block per all_to_all chunk with an
        # equal per-chunk budget (fixed-shape payload; see
        # collectives.wire_for_compressor).  topk_k is the GLOBAL budget,
        # so it is split across nd chunks AND num_buckets.
        block = n // nd
        kb = -(-topk_k // (nd * num_buckets))
        return SparseWire(k_per_block=min(block, kb), block_size=block,
                          value_dtype=value_dtype)
    if compressor == "identity":
        return DenseWire(value_dtype=value_dtype)
    raise ValueError(f"unknown compressor {compressor!r}")


@dataclasses.dataclass(frozen=True)
class PlanSpec:
    """One deployment configuration of the coded-compressed trainer.

    `num_ranks` is the coding-rank count the plan targets; it is optional at
    authoring time (the launcher fills it from the mesh), but when set it
    gates the per-rank budget length at CONSTRUCTION — a wrong-length
    `k_per_block` tuple fails here with a real message instead of surfacing
    as an opaque shape error inside jit.
    """

    d: int = 2                          # redundancy (copies per data shard)
    allocation: str = "uniform"         # uniform | rate_aware | exact_load
    compressor: str = "sign"            # sign | block_topk | topk | identity
    group_size: int = 512               # sign group (also phase-2 packing)
    k_per_block: Union[int, Tuple[int, ...]] = 8
    # ^ kept coords per block (block_topk); a per-rank tuple is a per-rank
    #   k budget (sim.cost_model.solve_k_budgets output)
    block_size: int = 256               # sparsification block (block_topk)
    topk_k: int = 64                    # global-K budget (compressor="topk")
    value_dtype: str = "float32"        # sparse values / dense payload dtype
    num_buckets: int = 1                # flat-vector split for comm overlap
    bucket_schedule: str = "pipelined"  # pipelined | serial
    backend: str = "auto"               # auto | pallas | jnp
    num_ranks: Optional[int] = None     # coding-rank count (None = unbound)

    def __post_init__(self):
        if isinstance(self.k_per_block, (list, tuple)):
            ks = tuple(self.k_per_block)
            if any(int(k) != k for k in ks):
                raise ValueError(f"per-rank k budgets must be integers, "
                                 f"got {ks}")
            # normalize to plain ints (solve_k_budgets hands back np ints)
            object.__setattr__(self, "k_per_block",
                               tuple(int(k) for k in ks))
        if self.d < 1:
            raise ValueError(f"redundancy d must be >= 1, got {self.d}")
        if self.allocation not in ALLOCATIONS:
            raise ValueError(f"unknown allocation {self.allocation!r}; "
                             f"have {ALLOCATIONS}")
        if self.compressor not in PLAN_COMPRESSORS:
            raise ValueError(f"unknown compressor {self.compressor!r}; "
                             f"have {PLAN_COMPRESSORS}")
        if self.group_size < 1:
            raise ValueError(f"group_size must be >= 1, got {self.group_size}")
        if self.block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {self.block_size}")
        if self.topk_k < 1:
            raise ValueError(f"topk_k must be >= 1, got {self.topk_k}")
        if self.num_buckets < 1:
            raise ValueError(f"num_buckets must be >= 1, "
                             f"got {self.num_buckets}")
        if self.bucket_schedule not in BUCKET_SCHEDULES:
            raise ValueError(f"unknown bucket_schedule "
                             f"{self.bucket_schedule!r}; "
                             f"have {BUCKET_SCHEDULES}")
        if self.backend not in PLAN_BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}; "
                             f"have {PLAN_BACKENDS}")
        if self.num_ranks is not None and self.num_ranks < 1:
            raise ValueError(f"num_ranks must be >= 1, got {self.num_ranks}")
        if self.num_ranks is not None and self.d > self.num_ranks:
            raise ValueError(f"redundancy d={self.d} exceeds the coding-rank "
                             f"count num_ranks={self.num_ranks}")
        if isinstance(self.k_per_block, tuple):
            if self.compressor != "block_topk":
                raise ValueError("per-rank k budgets (tuple k_per_block) "
                                 "require compressor='block_topk', got "
                                 f"{self.compressor!r}")
            if not self.k_per_block:
                raise ValueError("per-rank k budgets must be non-empty")
            if any(k < 1 for k in self.k_per_block):
                raise ValueError(f"per-rank k budgets must be ints >= 1, "
                                 f"got {self.k_per_block}")
            if (self.num_ranks is not None
                    and len(self.k_per_block) != self.num_ranks):
                raise ValueError(
                    f"per-rank k budgets have {len(self.k_per_block)} "
                    f"entries but the plan targets num_ranks="
                    f"{self.num_ranks} coding ranks; pass one k per rank")
        elif self.k_per_block < 1:
            raise ValueError(f"k_per_block must be >= 1, "
                             f"got {self.k_per_block}")

    # -- derivation ---------------------------------------------------------

    def wire(self, n: int = 0, nd: int = 1) -> WireFormat:
        """The WireFormat this plan ships for one bucket of `n` coords."""
        return build_wire(self.compressor, group_size=self.group_size,
                          k_per_block=self.k_per_block,
                          block_size=self.block_size, topk_k=self.topk_k,
                          value_dtype=self.value_dtype, n=n, nd=nd,
                          num_buckets=self.num_buckets)

    def coding_collective_config(self, coding_axes: Tuple[str, ...] = ("data",),
                                 phase2_dtype: str = "float32",
                                 phase2_sign: bool = False
                                 ) -> CodingCollectiveConfig:
        """The collective config the mesh step runs this plan with."""
        return CodingCollectiveConfig(coding_axes=tuple(coding_axes),
                                      group_size=self.group_size,
                                      phase2_dtype=jnp.dtype(phase2_dtype),
                                      phase2_sign=phase2_sign,
                                      backend=self.backend)

    def rank_wire_bytes(self, n: int,
                        num_ranks: Optional[int] = None) -> np.ndarray:
        """Per-rank phase-1 uplink bytes for an `n`-coord flat vector — the
        quantity StepTimer prices and benchmarks/comm_volume audits."""
        m = num_ranks if num_ranks is not None else self.num_ranks
        if m is None:
            raise ValueError("rank_wire_bytes needs num_ranks (pass it or "
                             "set PlanSpec.num_ranks)")
        return self.wire(n, 1).rank_wire_bytes(n, m)

    @property
    def overlap(self) -> bool:
        """Whether StepTimer should price the pipelined bucket overlap."""
        return self.bucket_schedule == "pipelined" and self.num_buckets > 1

    # -- serialization ------------------------------------------------------

    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        if isinstance(d["k_per_block"], tuple):
            d["k_per_block"] = list(d["k_per_block"])
        return {"schema": PLAN_SCHEMA, **d}

    def to_json(self, indent: Optional[int] = None) -> str:
        return json.dumps(self.to_dict(), indent=indent, sort_keys=True)

    @classmethod
    def from_dict(cls, obj: Dict[str, Any]) -> "PlanSpec":
        obj = dict(obj)
        schema = obj.pop("schema", PLAN_SCHEMA)
        if schema != PLAN_SCHEMA:
            raise ValueError(f"unknown plan schema {schema!r}; "
                             f"expected {PLAN_SCHEMA!r}")
        names = {f.name for f in dataclasses.fields(cls)}
        unknown = set(obj) - names
        if unknown:
            raise ValueError(f"unknown PlanSpec fields {sorted(unknown)}")
        if isinstance(obj.get("k_per_block"), list):
            obj["k_per_block"] = tuple(int(k) for k in obj["k_per_block"])
        return cls(**obj)

    @classmethod
    def from_json(cls, text: str) -> "PlanSpec":
        return cls.from_dict(json.loads(text))

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.to_json(indent=2) + "\n")

    @classmethod
    def load(cls, path: str) -> "PlanSpec":
        with open(path) as f:
            return cls.from_json(f.read())
