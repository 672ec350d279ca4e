"""Generic sklearn-compatible estimator facade (DESIGN.md §3.4).

One class serves all registered workloads (the paper deploys its four
implementations "as Scikit-learn estimator objects", §4; sklearn itself
is not installable offline, so the fit/predict/score/get_params protocol
is implemented directly and is duck-type compatible with pipelines).

``fit`` accepts either raw arrays (one placement per call, like the old
API) or a :class:`~repro.api.dataset.PimDataset` — the sweep path where
the placement is paid once per session.

The estimator is backend-portable (DESIGN.md §10): ``system=`` accepts
ANY :class:`~repro.systems.base.System` — the default ``PimSystem``, a
``HostSystem`` CPU baseline, or a ``ModeledGpuSystem`` — and the fit
runs there unmodified::

    make_estimator("linreg", version="fp32",
                   system=make_system("host")).fit(X, y)

(``pim=`` remains accepted as a deprecated alias for one PR.)

Hyperparameters flow through to the trainers untyped, so every knob the
workload registry declares is available here — including ``fuse_steps``
(DESIGN.md §9): ``make_estimator("linreg", version="int32",
fuse_steps=32).fit(ds)`` trains with 32 GD iterations compiled into each
``lax.scan`` launch, bit-identical to ``fuse_steps=1`` for the integer
versions and ~an order of magnitude faster wall-clock.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Optional

from ..obs.trace import TRACER
from ..systems import PimConfig, PimSystem, System
from .dataset import PimDataset
from .registry import FitResult, Workload, get_workload


def _default_system(n_cores: int = 16) -> PimSystem:
    return PimSystem(PimConfig(n_cores=n_cores))


def _resolve_system_kwarg(system: Optional[System],
                          pim: Optional[System]) -> Optional[System]:
    """Fold the deprecated ``pim=`` alias into ``system=`` (one
    DeprecationWarning per call site, pattern of core/estimators.py)."""
    if pim is not None:
        warnings.warn(
            "the pim= keyword is deprecated; pass system= (any "
            "repro.systems.System — PimSystem, HostSystem, "
            "ModeledGpuSystem)", DeprecationWarning, stacklevel=3)
        if system is None:
            system = pim
    return system


class PimEstimator:
    """sklearn-style facade over any registered workload."""

    def __init__(self, workload, version: Optional[str] = None,
                 n_cores: int = 16, pim: Optional[System] = None,
                 system: Optional[System] = None, **params):
        self.workload: Workload = (get_workload(workload)
                                   if isinstance(workload, str) else workload)
        # validate eagerly so a typo'd hyperparameter fails at construction
        spec = self.workload.spec(version, **params)
        self.version = spec.version
        system = _resolve_system_kwarg(system, pim)
        self.system: System = system or _default_system(n_cores)
        self.n_cores = self.system.config.n_cores
        self._params = dict(spec.params)
        self.result_: Optional[FitResult] = None

    # -- legacy alias --------------------------------------------------------

    @property
    def pim(self) -> System:
        """Deprecated name for :attr:`system` (kept for one PR)."""
        return self.system

    @pim.setter
    def pim(self, value: System) -> None:
        self.system = value
        self.n_cores = value.config.n_cores

    # -- sklearn parameter protocol -----------------------------------------

    def get_params(self, deep: bool = True) -> dict:
        out = {"version": self.version, "n_cores": self.n_cores}
        out.update(self._params)
        return out

    def set_params(self, **params) -> "PimEstimator":
        # validate the full candidate combination FIRST so a rejected
        # call leaves the estimator untouched
        version = params.pop("version", self.version)
        n_cores = params.pop("n_cores", None)
        system = _resolve_system_kwarg(params.pop("system", None),
                                       params.pop("pim", None))
        unknown = set(params) - set(self.workload.defaults)
        if unknown:
            raise ValueError(f"invalid parameters {sorted(unknown)} for "
                             f"{self.workload.name}")
        hyper = dict(self._params)
        hyper.update(params)
        self.workload.spec(version, **hyper)

        self.version = version
        self._params = hyper
        if n_cores is not None:
            # rebuild the session at the new core count, preserving the
            # rest of its config (system kind, reduce strategy, backend,
            # threads)
            self.n_cores = int(n_cores)
            self.system = type(self.system)(dataclasses.replace(
                self.system.config, n_cores=self.n_cores))
        if system is not None:
            self.system = system
            self.n_cores = self.system.config.n_cores
        return self

    # -- estimation protocol -------------------------------------------------

    def fit(self, X, y=None) -> "PimEstimator":
        with TRACER.span("repro.fit", "fit", "fit",
                         workload=self.workload.name, version=self.version):
            if isinstance(X, PimDataset):
                if y is not None:
                    raise ValueError(
                        "y must not be passed alongside a PimDataset — the "
                        "dataset already holds its labels; rebuild it with "
                        "System.put(X, y) to change them")
                # a dataset is bound to the system holding its shards;
                # training runs there.  Adopt it so the estimator's config
                # and stats refer to the system that actually trained.
                ds = X
                self.system = ds.system
                self.n_cores = self.system.config.n_cores
            else:
                ds = self.system.put(X, None if self.workload.unsupervised
                                     else y)
            spec = self.workload.spec(self.version, **self._params)
            self.result_ = self.workload.fit(ds, spec)
            for name, value in self.result_.attributes.items():
                setattr(self, name, value)
            return self

    def _fitted(self) -> FitResult:
        if self.result_ is None:
            raise RuntimeError(
                f"this {self.workload.name} estimator is not fitted yet; "
                f"call fit first")
        return self.result_

    def predict(self, X):
        return self.workload.predict(self._fitted(), X)

    def score(self, X, y=None) -> float:
        return self.workload.score(self._fitted(), X, y)

    def fit_predict(self, X, y=None):
        return self.fit(X, y).predict(
            X.X if isinstance(X, PimDataset) else X)

    # optional per-workload methods (classifiers expose probabilities)

    def decision_function(self, X):
        return self._optional("decision_function", X)

    def predict_proba(self, X):
        return self._optional("predict_proba", X)

    def _optional(self, method: str, X):
        fn = getattr(self.workload, method, None)
        if fn is None:
            raise AttributeError(
                f"{self.workload.name} does not implement {method}")
        return fn(self._fitted(), X)

    def __repr__(self) -> str:
        kv = ", ".join(f"{k}={v!r}" for k, v in self.get_params().items())
        return f"PimEstimator({self.workload.name!r}, {kv})"


def make_estimator(name: str, version: Optional[str] = None,
                   n_cores: int = 16, pim: Optional[System] = None,
                   system: Optional[System] = None,
                   **params) -> PimEstimator:
    """Construct an estimator for any registered workload by name.

    ``make_estimator("kmeans", version="int16", n_clusters=8)`` — pass
    ``system=`` to target a specific execution backend (PIM, host CPU,
    or the modeled GPU; DESIGN.md §10)."""
    return PimEstimator(get_workload(name), version=version,
                        n_cores=n_cores, pim=pim, system=system, **params)
