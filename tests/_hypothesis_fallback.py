"""Import-or-shim for `hypothesis`.

Test modules import `given`/`settings`/`st` from here: when `hypothesis` is
installed it is used verbatim; where it is not, a deterministic
single-example fallback runs each property test once at the midpoint of
every strategy's range — strictly weaker than real property testing, but
the assertions still execute and collection never fails on the import.
"""
from __future__ import annotations

try:
    from hypothesis import given, settings, strategies as st  # noqa: F401

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised only without hypothesis
    import functools
    import inspect

    HAVE_HYPOTHESIS = False

    class _Strategy:
        def __init__(self, example):
            self.example = example

    class _Strategies:
        @staticmethod
        def floats(lo: float, hi: float, **_kw) -> _Strategy:
            return _Strategy(lo + (hi - lo) / 2.0)

        @staticmethod
        def integers(lo: int, hi: int, **_kw) -> _Strategy:
            return _Strategy(lo + (hi - lo) // 2)

        @staticmethod
        def booleans() -> _Strategy:
            return _Strategy(True)

        @staticmethod
        def sampled_from(seq) -> _Strategy:
            return _Strategy(next(iter(seq)))

    st = _Strategies()

    def given(*strategies: _Strategy, **kw_strategies: _Strategy):
        def deco(fn):
            @functools.wraps(fn)
            def wrapper(*args, **kwargs):
                drawn = tuple(s.example for s in strategies)
                drawn_kw = {k: s.example for k, s in kw_strategies.items()}
                return fn(*args, *drawn, **kwargs, **drawn_kw)

            # hide the drawn parameters from pytest's fixture resolution
            # (functools.wraps would otherwise expose them via __wrapped__)
            del wrapper.__wrapped__
            sig = inspect.signature(fn)
            params = list(sig.parameters.values())
            keep = params[:len(params) - len(strategies)]
            keep = [p for p in keep if p.name not in kw_strategies]
            wrapper.__signature__ = sig.replace(parameters=keep)
            return wrapper

        return deco

    def settings(**_kw):
        def deco(fn):
            return fn

        return deco
