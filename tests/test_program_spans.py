"""The program's span helper (repro/spans.py): a leaf module of profiler
annotations named by constants, harmless with no profiler running and a
no-op without JAX; and the decode step's stable program name."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro import spans

SPANS_PY = Path(spans.__file__)


def test_names_are_constants_under_one_prefix():
    names = [v for k, v in vars(spans).items()
             if k.isupper() and k != "PREFIX" and isinstance(v, str)]
    assert len(names) == len(set(names)) == 17
    assert all(n.startswith("aquifer.") for n in names)


def test_a_span_runs_with_no_profiler():
    from jax.profiler import TraceAnnotation

    cm = spans.span(spans.RESTORE_INSTALL)
    assert isinstance(cm, TraceAnnotation)
    with cm:
        pass
    with pytest.raises(KeyError):        # exceptions pass through
        with spans.span(spans.RESTORE):
            raise KeyError("x")


def test_a_spanned_function_keeps_its_name_arguments_and_result():
    @spans.spanned(spans.RESTORE_HOT)
    def chunk(a, b=2):
        """doc"""
        return a * b

    assert chunk(3, b=4) == 12 and chunk.__name__ == "chunk" and chunk.__doc__ == "doc"


def test_the_helper_imports_nothing_else_of_the_package():
    """Core, kernels and serving all import it, so it stays a leaf."""
    code = ("import sys, repro.spans; "
            "print(' '.join(sorted(m for m in sys.modules if m.startswith('repro'))))")
    env = dict(os.environ, PYTHONPATH=str(SPANS_PY.parents[1]))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, check=True, env=env)
    assert out.stdout.split() == ["repro", "repro.spans"]


def test_a_span_is_a_no_op_without_jax(monkeypatch):
    monkeypatch.setitem(sys.modules, "jax", None)
    monkeypatch.setitem(sys.modules, "jax.profiler", None)
    spec = importlib.util.spec_from_file_location("spans_without_jax", SPANS_PY)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod._Annotation is None
    with mod.span(mod.RESTORE_HOT):
        pass
    assert mod.spanned(mod.RESTORE_HOT)(lambda: 5)() == 5


def test_the_decode_step_program_is_named():
    import jax
    import jax.numpy as jnp

    from repro.configs.base import get_config
    from repro.models.model_zoo import build
    from repro.serve.engine import _decode_jit, new_instance

    cfg = get_config("phi4-mini-3.8b").reduced(vocab=256)
    inst = new_instance(cfg, build(cfg).init(jax.random.PRNGKey(0)), 1, 8)
    lowered = _decode_jit(inst.model).lower(
        inst.params, jnp.zeros((1, 1), jnp.int32), inst.caches, jnp.int32(0))
    assert "jit_decode_step" in lowered.as_text()
