"""``repro.api`` — the one front door to the engine.

One config, one factory::

    from repro.api import IndexConfig, open_index

    index = open_index(IndexConfig(n=30_000, capacity=65_536))
    index.insert_many(ids, idx, val)
    server = QueryServer(index, k=10)
    result = server.query(q_idx, q_val)        # -> QueryResult

:func:`open_index` replaces the four constructor permutations the system
grew (``SinnamonIndex``, ``ShardedSinnamonIndex``, ``DurableSinnamonIndex``,
``DurableShardedSinnamonIndex``) with a single declarative
:class:`IndexConfig`:

* ``shards`` picks single-device vs mesh-sharded SPMD serving (capacity is
  always the GLOBAL slot count; per-shard sizing is derived),
* ``durability`` (a :class:`DurabilityConfig` block) turns on the
  WAL + snapshot + recovery machinery — ``open_index`` then *recovers*
  existing state instead of starting empty,
* ``backend`` pins the scoring backend for every search on the returned
  index, subsuming the ``REPRO_SCORE_BACKEND`` env var (which remains the
  process-wide default when ``backend`` is None).

The legacy constructors keep working — they are exactly what the factory
routes to — and ``tests/test_api_facade.py`` asserts each one produces the
same state as its :func:`open_index` spelling.  New code (the launcher, the
examples, the async front door in ``repro.serving.frontend``) goes through
the facade.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from repro.core import engine as eng
from repro.serving.results import QueryResult, new_trace_id

__all__ = [
    "DurabilityConfig",
    "IndexConfig",
    "QueryResult",
    "new_trace_id",
    "open_index",
]


@dataclasses.dataclass(frozen=True)
class DurabilityConfig:
    """WAL + snapshot policy block of an :class:`IndexConfig`.

    Presence of this block makes :func:`open_index` return a durable index
    (``repro.persist``): every mutation is logged before it is applied and
    opening again on the same directories recovers snapshot + WAL tail.
    """

    wal_dir: str
    snapshot_dir: Optional[str] = None
    snapshot_every: Optional[int] = None   # snapshot after N logged ops
    compact_threshold: Optional[float] = None  # compact when drift exceeds
    compact_check_every: int = 64
    fsync: bool = True
    segment_bytes: int = 4 << 20
    snapshot_keep: int = 3

    def __post_init__(self):
        if self.snapshot_every is not None and self.snapshot_dir is None:
            raise ValueError("snapshot_every requires snapshot_dir "
                             "(periodic snapshots need somewhere to go)")

    def kwargs(self) -> dict:
        """Keyword arguments for the Durable* constructors."""
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class IndexConfig:
    """Declarative index configuration; the input to :func:`open_index`.

    Engine geometry (the paper's levers — see docs/levers.md):

    * ``n`` — ambient dimensionality; ``capacity`` — GLOBAL document slots;
      ``max_nnz`` — padded CSR width (max ψ_d); ``m``/``h`` — sketch size /
      hash count.
    * ``sketch_kind`` — ``full | lite`` (§3.3 half sketch);
      ``cell_dtype`` — sketch cell storage (``f32 | bf16 | f8``);
      ``store_dtype`` — raw VecStore width the exact rerank reads.
    * ``positive_only`` (Sinnamon+), ``index_buckets`` (§4.1.2 hashed
      inverted index), ``seed``.

    Deployment shape:

    * ``backend`` — scoring backend for every search on this index
      (``reference | grouped | pallas``; None → the process default, i.e.
      ``REPRO_SCORE_BACKEND`` or pallas).
    * ``shards`` — >1 serves the mesh-sharded SPMD index over the first
      ``shards`` devices: chips on an accelerator, forced host devices on
      the CPU platform (pass an explicit ``mesh`` to :func:`open_index` for
      other layouts).
    * ``durability`` — optional :class:`DurabilityConfig` block.
    * ``device_budget_mb`` — cap on the PER-DEVICE bytes of raw vector
      rows; setting it serves the hot/cold tiered index (sketches stay
      fully resident, raw CSR rows page between a device chunk cache and
      host RAM — see docs/tiering.md).  Results are bit-identical to the
      resident index.  ``tier_chunk_slots`` is the paging granularity in
      slots per chunk.
    """

    n: int
    capacity: int
    m: int = 60
    h: int = 1
    max_nnz: int = 256
    positive_only: bool = False
    index_buckets: Optional[int] = None
    sketch_kind: str = "full"
    cell_dtype: str = "bf16"
    store_dtype: str = "bfloat16"
    seed: int = 0
    backend: Optional[str] = None
    shards: int = 1
    update_block: int = 32
    durability: Optional[DurabilityConfig] = None
    device_budget_mb: Optional[float] = None   # per-device raw-store budget
    tier_chunk_slots: int = 256                # slots per tiering chunk

    def __post_init__(self):
        if self.shards < 1:
            raise ValueError(f"shards must be >= 1, got {self.shards}")
        if self.capacity < 1:
            raise ValueError(f"capacity must be positive, got {self.capacity}")
        if self.backend is not None:
            from repro.kernels import ops as _ops
            _ops.resolve_backend(self.backend)     # validate eagerly
        if self.device_budget_mb is not None and self.device_budget_mb <= 0:
            raise ValueError(f"device_budget_mb must be positive, "
                             f"got {self.device_budget_mb}")
        if self.tier_chunk_slots < 1:
            raise ValueError(f"tier_chunk_slots must be >= 1, "
                             f"got {self.tier_chunk_slots}")

    @property
    def local_capacity(self) -> int:
        """Per-shard slot count: ceil(capacity / shards), rounded up to 32."""
        per = -(-self.capacity // self.shards)
        return ((per + 31) // 32) * 32

    def engine_spec(self) -> eng.EngineSpec:
        """The per-shard :class:`EngineSpec` this config describes.

        For ``shards == 1`` this is also the global spec (capacity rounded
        up to the engine's multiple-of-32 requirement).
        """
        return eng.EngineSpec(
            n=self.n, m=self.m, h=self.h, capacity=self.local_capacity,
            max_nnz=self.max_nnz, positive_only=self.positive_only,
            index_buckets=self.index_buckets, sketch_kind=self.sketch_kind,
            dtype=self.cell_dtype, value_dtype=self.store_dtype,
            seed=self.seed)


def _host_mesh(shards: int):
    import jax

    from repro.distributed import mesh as meshlib
    if shards == 1:
        return meshlib.single_device_mesh(("data", "model"))
    n_dev = len(jax.devices())
    if n_dev < shards:
        raise RuntimeError(
            f"IndexConfig.shards={shards} but only {n_dev} device(s) are "
            f"visible; on an accelerator each shard needs its own chip; on "
            f"the CPU platform force host devices BEFORE importing jax, e.g. "
            f'os.environ["XLA_FLAGS"] = '
            f'"--xla_force_host_platform_device_count={shards}"; or pass an '
            f"explicit mesh to open_index")
    return meshlib.make_mesh((1, shards), ("data", "model"))


def open_index(config: IndexConfig, *, mesh=None):
    """Open (or recover) the index a config describes.

    Routing:

    ========== ============ ==========================================
    durability shards/mesh  returns
    ========== ============ ==========================================
    None       1, no mesh   ``SinnamonIndex``
    None       >1 or mesh   ``ShardedSinnamonIndex``
    set        1, no mesh   ``DurableSinnamonIndex.open`` (recovers)
    set        >1 or mesh   ``DurableShardedSinnamonIndex.open``
    ========== ============ ==========================================

    With ``device_budget_mb`` set, each row routes to its Tiered* twin
    (``TieredSinnamonIndex`` / ``TieredShardedSinnamonIndex`` /
    ``DurableTieredSinnamonIndex``); durable + sharded + tiered is not
    implemented yet and raises ``NotImplementedError``.

    ``mesh`` overrides the host-local mesh that ``shards > 1`` would build
    (and forces the sharded path even for one shard — the 1×1 mesh runs the
    same shard_map program as production).  The returned index carries
    ``config`` on ``.config`` and ``config.backend`` as its default scoring
    backend, so callers never touch ``REPRO_SCORE_BACKEND``.
    """
    spec = config.engine_spec()
    sharded = mesh is not None or config.shards > 1
    tiered = config.device_budget_mb is not None
    if sharded and tiered and config.durability is not None:
        raise NotImplementedError(
            "durability + shards + device_budget_mb is not supported yet: "
            "drop one of the three (tiered sharded serving is available "
            "without durability)")
    if sharded and mesh is None:
        mesh = _host_mesh(config.shards)
    tkw = dict(tier_chunk_slots=config.tier_chunk_slots,
               device_budget_bytes=int(config.device_budget_mb * (1 << 20))
               ) if tiered else {}

    if config.durability is None:
        if sharded and tiered:
            from repro.serving.sharded import TieredShardedSinnamonIndex
            index = TieredShardedSinnamonIndex(
                spec, mesh, update_block=config.update_block, **tkw)
        elif sharded:
            from repro.serving.sharded import ShardedSinnamonIndex
            index = ShardedSinnamonIndex(spec, mesh,
                                         update_block=config.update_block)
        elif tiered:
            index = eng.TieredSinnamonIndex(spec, **tkw)
        else:
            index = eng.SinnamonIndex(spec)
    else:
        dkw = config.durability.kwargs()
        if sharded:
            from repro.persist import DurableShardedSinnamonIndex
            index = DurableShardedSinnamonIndex.open(
                spec, mesh, update_block=config.update_block, **dkw)
        elif tiered:
            from repro.persist.durable import DurableTieredSinnamonIndex
            index = DurableTieredSinnamonIndex.open(spec, **dkw, **tkw)
        else:
            from repro.persist import DurableSinnamonIndex
            index = DurableSinnamonIndex.open(spec, **dkw)

    index.default_backend = config.backend
    index.config = config
    return index
