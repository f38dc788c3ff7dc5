"""Shared test configuration.

Three concerns live here:

* **Optional-dev-dep fallback** — the property-test modules do
  ``from hypothesis import given, settings, strategies as st`` at import
  time.  When ``hypothesis`` (a dev extra, see pyproject.toml) is not
  installed, a *mini* implementation is installed in its place that
  actually **executes** every ``@given`` test with deterministic
  pseudo-random examples (seeded per test from ``--hypothesis-seed``),
  instead of the old skip-stub — the property layer guards the
  compaction subsystem even without the real dependency.  The fallback
  supports the strategy surface this suite uses (``integers``,
  ``floats``, ``booleans``, ``sampled_from``); anything else skips with
  a clear message.  Example counts are capped (default 8, override via
  ``REPRO_MINI_HYPOTHESIS_EXAMPLES``) so tier-1 stays fast; CI installs
  real hypothesis and runs the full declared ``max_examples`` with a
  fixed ``--hypothesis-seed`` for reproducibility.
* **``slow`` marker** — the dry-run suites compile reduced transformer
  programs on 512 forced host devices (minutes per fixture).  They are
  skipped by default and enabled with ``--runslow`` or ``RUN_SLOW=1`` so
  the default tier-1 command stays fast.
* **``--update-golden``** — rewrites the golden-trace artifacts under
  tests/golden/ (see tests/test_golden_trace.py) instead of comparing
  against them.
"""
from __future__ import annotations

import os
import random
import sys
import types
import zlib

import pytest

_HAVE_REAL_HYPOTHESIS = True
_MINI_SEED = [0]  # filled from --hypothesis-seed in pytest_configure


def _mini_example_cap() -> int:
    return int(os.environ.get("REPRO_MINI_HYPOTHESIS_EXAMPLES", "8"))


class _MiniStrategy:
    """A drawable strategy of the mini-hypothesis fallback."""

    def __init__(self, name: str, draw=None):
        self.name = name
        self._draw = draw

    def draw(self, rng: random.Random):
        if self._draw is None:
            pytest.skip(f"strategy {self.name} is not supported by the "
                        "mini-hypothesis fallback (pip install .[dev])")
        return self._draw(rng)

    def __repr__(self):
        return f"<mini-hypothesis strategy {self.name}>"


def _mini_strategies() -> types.ModuleType:
    st = types.ModuleType("hypothesis.strategies")
    st.__stub__ = True  # marker for debugging / schema tests

    def integers(min_value, max_value):
        return _MiniStrategy(
            f"integers({min_value}, {max_value})",
            lambda rng: rng.randint(min_value, max_value))

    def floats(min_value, max_value, **_kw):
        return _MiniStrategy(
            f"floats({min_value}, {max_value})",
            lambda rng: rng.uniform(min_value, max_value))

    def booleans():
        return _MiniStrategy("booleans()", lambda rng: rng.random() < 0.5)

    def sampled_from(elements):
        seq = list(elements)
        return _MiniStrategy(f"sampled_from({seq!r})",
                             lambda rng: seq[rng.randrange(len(seq))])

    st.integers = integers
    st.floats = floats
    st.booleans = booleans
    st.sampled_from = sampled_from
    # Unknown strategies degrade to a clean per-test skip, never a
    # collection error.
    st.__getattr__ = lambda name: (  # PEP 562
        lambda *a, **k: _MiniStrategy(name))
    return st


def _install_hypothesis_fallback() -> None:
    global _HAVE_REAL_HYPOTHESIS
    try:
        import hypothesis  # noqa: F401
        return
    except ModuleNotFoundError:
        _HAVE_REAL_HYPOTHESIS = False

    mod = types.ModuleType("hypothesis")
    mod.__stub__ = True

    def given(*_args, **strategies):
        if _args:
            raise TypeError(
                "mini-hypothesis fallback supports keyword strategies "
                "only — write @given(x=st.integers(...)) or install the "
                "real dependency (pip install .[dev])")

        def deco(fn):
            def runner(*args, **kwargs):
                cfg = getattr(runner, "_mini_settings", None) or \
                    getattr(fn, "_mini_settings", None) or {}
                n_examples = min(cfg.get("max_examples", 25),
                                 _mini_example_cap())
                base = zlib.crc32(fn.__qualname__.encode()) ^ _MINI_SEED[0]
                for i in range(n_examples):
                    rng = random.Random(base + i)
                    example = {k: s.draw(rng)
                               for k, s in strategies.items()}
                    try:
                        fn(*args, **example, **kwargs)
                    except Exception:
                        print(f"\nmini-hypothesis falsifying example "
                              f"(seed {_MINI_SEED[0]}, #{i}): {example}",
                              file=sys.stderr)
                        raise

            runner.__name__ = getattr(fn, "__name__", "hypothesis_test")
            runner.__doc__ = getattr(fn, "__doc__", None)
            return runner

        return deco

    def settings(*_args, **kwargs):
        def deco(fn):
            fn._mini_settings = kwargs
            return fn

        return deco

    st = _mini_strategies()
    mod.given = given
    mod.settings = settings
    mod.strategies = st
    sys.modules["hypothesis"] = mod
    sys.modules["hypothesis.strategies"] = st


_install_hypothesis_fallback()


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="run tests marked slow (multi-minute dry-run compiles)")
    parser.addoption(
        "--update-golden", action="store_true", default=False,
        help="rewrite the golden-trace artifacts under tests/golden/")
    if not _HAVE_REAL_HYPOTHESIS:
        # Real hypothesis registers this itself; the fallback accepts the
        # same flag so CI/local commands stay identical.
        parser.addoption(
            "--hypothesis-seed", action="store", default="0",
            help="base seed of the mini-hypothesis fallback examples")


def pytest_configure(config):
    if not _HAVE_REAL_HYPOTHESIS:
        try:
            _MINI_SEED[0] = int(config.getoption("--hypothesis-seed"))
        except (TypeError, ValueError):
            _MINI_SEED[0] = 0


def pytest_collection_modifyitems(config, items):
    run_slow = os.environ.get("RUN_SLOW", "").lower() not in ("", "0",
                                                              "false")
    if config.getoption("--runslow") or run_slow:
        return
    skip = pytest.mark.skip(
        reason="slow compile test (enable with --runslow or RUN_SLOW=1)")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(scope="session")
def chip_smoke():
    """The repo-root ``chip_smoke.py`` as a module (it is not a package
    module); importing it touches no device."""
    import importlib.util

    if "chip_smoke" not in sys.modules:
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "chip_smoke.py")
        spec = importlib.util.spec_from_file_location("chip_smoke", path)
        module = importlib.util.module_from_spec(spec)
        sys.modules["chip_smoke"] = module
        spec.loader.exec_module(module)
    return sys.modules["chip_smoke"]
