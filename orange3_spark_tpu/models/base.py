"""MLlib-style Estimator / Transformer / Pipeline protocol.

The reference exposes ``pyspark.ml.Estimator.fit(df) -> Model`` and
``Transformer.transform(df) -> df``, with hyper-parameters as introspectable
``Param`` objects that the add-on uses to auto-generate widget GUIs
(SURVEY.md §2b "Estimator/Transformer/Pipeline API"; reconstructed, mount
empty — the auto-generation-from-params pattern is the add-on's signature
design and is preserved here). TPU-native redesign: params are frozen
dataclasses (hashable → usable as jit static args; introspectable via
``dataclasses.fields`` → widget auto-generation in widgets/autogen.py), and a
fitted Model is a host object wrapping a **pytree of device arrays** so it
can be checkpointed, donated, and passed through staged workflow graphs.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Sequence

import jax
import numpy as np

from orange3_spark_tpu.core.table import TpuTable


def _serve_routed(kind: str, raw_fn):
    """Route a subclass-defined ``transform``/``predict`` through the
    serving path (serve/context.py) when a ServingContext is active.
    With no active context this is one None-check of overhead; inside a
    serving trace the per-thread reentrancy guard short-circuits straight
    to the raw method."""

    @functools.wraps(raw_fn)
    def wrapper(self, *args, **kwargs):
        from orange3_spark_tpu.serve.context import route

        return route(kind, raw_fn, self, *args, **kwargs)

    wrapper.__serve_raw__ = raw_fn
    return wrapper


@dataclasses.dataclass(frozen=True)
class Params:
    """Base for estimator hyper-parameter dataclasses.

    Frozen (hashable) so a params instance can be a jit static argument and a
    dict key in compile caches. ``describe()`` yields (name, type, default)
    triples — the introspection surface the widget auto-generator consumes,
    playing the role of ``pyspark.ml.param.Param`` metadata in the reference.
    """

    def replace(self, **kwargs) -> "Params":
        return dataclasses.replace(self, **kwargs)

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def describe(cls) -> list[tuple[str, type, Any]]:
        return [(f.name, f.type, f.default) for f in dataclasses.fields(cls)]


class HasParams:
    """The one params-dataclass constructor: subclasses declare ``ParamsCls``
    and get ``Cls(**kwargs)`` / ``Cls(params)`` / ``Cls(params, override=...)``
    for free. Shared by Transformer, Estimator, and the fit-less algorithm
    entry points (PrefixSpan, PowerIterationClustering)."""

    ParamsCls: type["Params"] | None = None

    def __init__(self, params: "Params | None" = None, **kwargs):
        if self.ParamsCls is None:
            if params is not None or kwargs:
                raise TypeError(f"{type(self).__name__} takes no params")
            return
        if params is None:
            params = self.ParamsCls(**kwargs)
        elif kwargs:
            params = params.replace(**kwargs)
        self.params = params


def concrete_or_none(x, cast=float):
    """``cast(x)`` for concrete device scalars, ``None`` under a jit trace.

    Fit methods record host-side convenience scalars (``n_iter_``,
    ``training_cost_``) — pure diagnostics, not model state. When a fit runs
    INSIDE a trace (staged refit, workflow/staging.py ``refit=True``), those
    reads would force a concretization error; the honest value there is
    "not available", not a crash."""
    if isinstance(x, jax.core.Tracer):
        return None
    return cast(x)


class Transformer(HasParams):
    """transform(table) -> table. Stateless or carrying fitted state.

    Subclasses that declare ``ParamsCls`` get the standard params-dataclass
    constructor from HasParams; ones with custom state define their own
    __init__.

    Every subclass-defined ``transform``/``predict`` is wrapped at class
    creation to route through the serving subsystem (serve/) when a
    ``ServingContext`` is active — shape-bucketed padding, AOT executable
    cache, optional micro-batching. Without a context the raw method runs
    untouched.
    """

    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        for kind in ("transform", "predict"):
            fn = cls.__dict__.get(kind)
            if fn is not None and callable(fn) \
                    and not hasattr(fn, "__serve_raw__"):
                setattr(cls, kind, _serve_routed(kind, fn))

    def transform(self, table: TpuTable) -> TpuTable:
        raise NotImplementedError

    def __call__(self, table: TpuTable) -> TpuTable:
        return self.transform(table)


class Model(Transformer):
    """A fitted model: hyper-params + a pytree of device arrays.

    Subclasses set ``self.params`` and expose fitted state through
    ``state_pytree`` for checkpointing (utils/checkpoint.py). Pickling
    converts every jax array (including ones nested in pytrees like tree
    ensembles) to numpy so checkpoints are host-portable; jnp ops re-promote
    them lazily on first use after load.
    """

    params: Params
    #: device values a fit computed beside ``state_pytree`` and that no
    #: transform needs (KMeans: cost, iterations run, cluster sizes, the
    #: initial centres it drew); a staged refit hands them out of its one
    #: program with the state (workflow/staging.py)
    fit_summary: dict = {}

    def __getstate__(self):
        return jax.tree.map(
            lambda x: np.asarray(x) if isinstance(x, jax.Array) else x,
            dict(self.__dict__),
            is_leaf=lambda x: isinstance(x, jax.Array) or not isinstance(
                x, (dict, list, tuple)
            ),
        )

    def __setstate__(self, state):
        self.__dict__.update(state)

    @property
    def state_pytree(self) -> dict[str, Any]:
        raise NotImplementedError

    def _touch_serving_state(self) -> None:
        """Move the serving fingerprint after an in-place state change:
        the AOT cache bakes fitted state into compiled programs
        (serve/context folds this version into the model fingerprint), so
        every ``load_state_pytree`` — base or override — must call this."""
        self._serve_state_version = (
            getattr(self, "_serve_state_version", 0) + 1)

    def _serve_state_token(self):
        """The version token serve/context folds into the fingerprint.
        Containers (PipelineModel, OneVsRestModel) include their
        children's tokens: reloading a NESTED sub-model must move the
        container's key too — its executables bake the child state in."""
        return getattr(self, "_serve_state_version", 0)

    def load_state_pytree(self, state: dict[str, Any]) -> None:
        for k, v in state.items():
            setattr(self, k, v)
        self._touch_serving_state()

    def load_fit_summary(self, summary: dict[str, Any],
                         fit: str = "eager") -> None:
        """Take a fit's summary: from ``_fit`` itself (tracers under a
        staged refit) and again, concrete and with ``fit='staged'``, when
        a staged refit's states come back. Models with host-side mirrors
        of it override this."""
        self.fit_summary = summary


class Estimator:
    """fit(table) -> Model.  Subclasses define ``ParamsCls`` and ``_fit``."""

    ParamsCls: type[Params] = Params

    def __init__(self, params: Params | None = None, **kwargs):
        if params is None:
            params = self.ParamsCls(**kwargs)
        elif kwargs:
            params = params.replace(**kwargs)
        self.params = params
        self.last_fit_metrics: dict[str, float] = {}

    def fit(self, table: TpuTable) -> Model:
        from orange3_spark_tpu.obs.trace import refreshed_enabled as obs_enabled
        from orange3_spark_tpu.obs.trace import span

        # the outer obs bracket rides the OTPU_OBS kill-switch: under
        # OTPU_OBS=0 no report is built (its counter snapshots are the
        # only per-fit obs cost here). unique=True: a streaming _fit's
        # fit_stream opens its own richer "fit" span — record only the
        # outermost so traces never show fit ⊃ fit.
        report = None
        if obs_enabled():
            from orange3_spark_tpu.obs.report import RunReport

            report = RunReport("fit", estimator=type(self).__name__,
                               n_rows=table.n_rows)
        from orange3_spark_tpu.obs.context import trace_scope

        t0 = time.perf_counter()
        # mint the fit's run id here (reused — not shadowed — by a
        # streaming _fit's own @traced("fit") entry), so every span and
        # typed anomaly under this fit carries one identity
        with trace_scope("fit", reuse=True):
            with span("fit", unique=True, estimator=type(self).__name__):
                model = self._fit(table)
                if isinstance(model, Model):
                    try:
                        # don't time async dispatch
                        jax.block_until_ready(model.state_pytree)
                    except NotImplementedError:
                        pass
        # else: stateless result (e.g. QuantileDiscretizer -> Bucketizer)
        dt = time.perf_counter() - t0
        # rows/sec/chip is THE baseline metric (BASELINE.json "metric").
        # NOTE: first call includes XLA compile; benchmark harnesses must warm
        # up (bench.py fits twice and reports the second timing).
        n_chips = table.session.n_devices
        self.last_fit_metrics = {
            "fit_seconds": dt,
            "rows_per_sec_per_chip": table.n_rows / dt / max(n_chips, 1),
        }
        if report is not None and isinstance(model, Model):
            # a streaming _fit already attached its richer fit_stream
            # report — the outer bracket must not clobber it
            if getattr(model, "run_report_", None) is None:
                model.run_report_ = report.finish()
        return model

    def _fit(self, table: TpuTable) -> Model:
        raise NotImplementedError

    def fit_transform(self, table: TpuTable) -> TpuTable:
        return self.fit(table).transform(table)

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return f"{type(self).__name__}({self.params})"


class Pipeline(Estimator):
    """Chain of estimators/transformers (pyspark.ml.Pipeline equivalent)."""

    def __init__(self, stages: Sequence[Estimator | Transformer]):
        super().__init__(Params())
        self.stages = list(stages)

    def _fit(self, table: TpuTable) -> "PipelineModel":
        fitted: list[Transformer] = []
        for stage in self.stages:
            if isinstance(stage, Estimator):
                model = stage.fit(table)
                fitted.append(model)
                table = model.transform(table)
            else:
                fitted.append(stage)
                table = stage.transform(table)
        return PipelineModel(fitted)


class PipelineModel(Model):
    def __init__(self, stages: Sequence[Transformer]):
        self.params = Params()
        self.stages = list(stages)

    def transform(self, table: TpuTable) -> TpuTable:
        for stage in self.stages:
            table = stage.transform(table)
        return table

    @property
    def state_pytree(self) -> dict[str, Any]:
        return {
            f"stage{i}": s.state_pytree
            for i, s in enumerate(self.stages)
            if isinstance(s, Model)
        }

    def load_state_pytree(self, state: dict[str, Any]) -> None:
        for key, sub in state.items():
            idx = int(key.removeprefix("stage"))
            stage = self.stages[idx]
            if not isinstance(stage, Model):
                raise ValueError(f"checkpoint has state for non-model stage {idx}")
            stage.load_state_pytree(sub)
        # the pipeline itself can be the served object (its executables
        # bake STAGE state), so its fingerprint must move too
        self._touch_serving_state()

    def _serve_state_token(self):
        return (getattr(self, "_serve_state_version", 0),
                tuple(s._serve_state_token() for s in self.stages
                      if isinstance(s, Model)))


def infer_class_values(table: TpuTable) -> tuple[str, ...]:
    """Class labels from the domain, or '0'..'max(y)' when untyped.

    The fallback max only looks at LIVE rows (W > 0) — filtered rows' labels
    must not inflate the class count.
    """
    import jax.numpy as jnp

    cvar = table.domain.class_var
    from orange3_spark_tpu.core.domain import DiscreteVariable

    if isinstance(cvar, DiscreteVariable) and cvar.values:
        return tuple(cvar.values)
    y_max = jnp.max(jnp.where(table.W > 0, table.y, 0.0))
    return tuple(str(i) for i in range(int(np.asarray(y_max).item()) + 1))


def predictions_to_numpy(table: TpuTable, column: str = "prediction") -> np.ndarray:
    """Collect one prediction column to host, stripping padding.

    Padding is stripped from the VALIDITY MASK, not just ``n_rows``: a
    serving-bucketed table whose caller did not track the logical row
    count (``n_rows == n_pad``) still carries W == 0 on every pad row, so
    the trailing zero-weight run is trimmed too. Interior zero-weight
    rows (``filter()``ed) are logical rows and are kept.

    Carve-out: on an exactly pad-aligned table a trailing zero-weight run
    is INDISTINGUISHABLE from trailing ``filter()``ed logical rows, and
    this function treats it as padding. Callers that filter trailing rows
    and need them back must track the logical row count (``n_rows <
    n_pad``) — that branch returns every logical row unconditionally."""
    col = np.asarray(jax.device_get(table.column(column)))[: table.n_rows]
    if table.n_rows < table.n_pad:
        # caller tracked the row count; pads already sliced away above —
        # every logical row is returned even if filter() zeroed them all
        return col
    W = np.asarray(jax.device_get(table.W))[: table.n_rows]
    live = np.flatnonzero(W > 0)
    if live.size == 0:
        return col[:0]
    return col[: int(live[-1]) + 1]
