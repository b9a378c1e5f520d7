"""TpuSession — the SparkSession/SparkContext equivalent.

In the reference, an OWSparkContext-style environment widget builds a
SparkConf, calls ``SparkSession.builder.getOrCreate()`` and publishes the
session to every downstream widget (SURVEY.md §3 step 2; reconstructed — the
reference mount was empty). Here the "cluster" is a ``jax.sharding.Mesh``:
the session owns the mesh, the canonical data-parallel axis name, and the
sharding helpers everything else uses. Multi-host initialization maps to
``jax.distributed.initialize()`` exactly where Spark would connect to a
cluster manager.
"""

from __future__ import annotations

import contextlib
import contextvars
import os
import threading
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"


class TpuSession:
    """Owns the device mesh and shardings; get-or-create singleton like SparkSession.

    Axes:
      * ``data``  — batch/row dimension, the only parallelism the reference's
        Spark backend has (rows partitioned across executors).
      * ``model`` — optional second axis for wide coefficient/factor sharding
        (new capability beyond the reference; size 1 by default).
    """

    #: Session-level cache-precision policy (io/codec.py): what an
    #: estimator's ``cache_dtype='auto'`` resolves to. 'packed' = full
    #: compression (bf16 floats + lossless bit-packed ints — ~2x cache/
    #: spill/DMA capacity); assign 'f32' to opt a whole session back onto
    #: the legacy layout. The per-fit ``OTPU_CACHE_DTYPE`` env kill-switch
    #: overrides BOTH this and the param, and resolves ONCE at fit
    #: entry into a static jit argument.
    default_cache_dtype: str = "packed"

    _lock = threading.Lock()
    _active: "TpuSession | None" = None
    # per-context override installed by use(); isolates concurrent threads /
    # async tasks from each other and from the global get-or-create singleton
    _ctx_active: "contextvars.ContextVar[TpuSession | None]" = contextvars.ContextVar(
        "tpu_session_ctx", default=None
    )

    def __init__(
        self,
        mesh: Mesh | None = None,
        *,
        data_axis: str = DATA_AXIS,
        model_axis: str = MODEL_AXIS,
    ):
        if mesh is None:
            mesh = self.default_mesh()
        self.mesh = mesh
        self.data_axis = data_axis
        self.model_axis = model_axis if model_axis in mesh.axis_names else None

    # ------------------------------------------------------------------ mesh
    @staticmethod
    def default_mesh(devices: Sequence[jax.Device] | None = None) -> Mesh:
        devices = list(devices if devices is not None else jax.devices())
        return Mesh(np.asarray(devices).reshape(len(devices), 1), (DATA_AXIS, MODEL_AXIS))

    @classmethod
    def builder_get_or_create(cls, mesh: Mesh | None = None) -> "TpuSession":
        """``SparkSession.builder.getOrCreate()`` analogue."""
        with cls._lock:
            if cls._active is None or (mesh is not None and mesh != cls._active.mesh):
                cls._active = cls(mesh)
            return cls._active

    # Spark-flavored alias so ported user code reads naturally.
    get_or_create = builder_get_or_create

    @classmethod
    def active(cls) -> "TpuSession":
        ctx = cls._ctx_active.get()
        return ctx if ctx is not None else cls.builder_get_or_create()

    @classmethod
    def stop(cls) -> None:
        with cls._lock:
            cls._active = None

    @staticmethod
    def initialize_distributed(**kwargs) -> None:
        """Multi-host bring-up; the SparkContext→cluster-manager connection.

        No-op when running single-process (the common test path).
        """
        if int(os.environ.get("JAX_NUM_PROCESSES", "1")) > 1:  # pragma: no cover
            jax.distributed.initialize(**kwargs)

    @staticmethod
    def enable_compilation_cache() -> dict:
        """Persist compiled XLA programs across processes (Spark has no
        analogue — its tasks are interpreted; our "tasks" cost minutes of
        XLA compile, paid once per PROCESS without this). The cache lives
        where ``JAX_COMPILATION_CACHE_DIR`` says, else at the fixed
        ``<repo>/.jax_cache``. Returns the info dict for
        ``exec.compile_cache.cache_report`` (the bench line's ``cache_hit``
        field). Call once, before the first jit."""
        from orange3_spark_tpu.exec.compile_cache import (
            enable_compilation_cache,
        )

        return enable_compilation_cache()

    # ------------------------------------------------------------- shardings
    @property
    def n_devices(self) -> int:
        return self.mesh.size

    @property
    def data_parallelism(self) -> int:
        return self.mesh.shape[self.data_axis]

    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    @property
    def row_sharding(self) -> NamedSharding:
        """Rows split over the data axis, columns replicated: P('data', None)."""
        return NamedSharding(self.mesh, P(self.data_axis, None))

    @property
    def vector_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(self.data_axis))

    @property
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def pad_rows(self, n: int) -> int:
        """Smallest padded row count that divides evenly over the data axis.

        XLA wants equal shards; ragged rows are padded and masked via the
        table's weight column (Spark instead just has uneven partitions).
        """
        dp = self.data_parallelism
        return max(dp, -(-n // dp) * dp)

    @contextlib.contextmanager
    def use(self):
        """Install as the active session within this context (thread/task-local,
        so concurrent use() blocks can't clobber each other's view)."""
        token = TpuSession._ctx_active.set(self)
        try:
            yield self
        finally:
            TpuSession._ctx_active.reset(token)
