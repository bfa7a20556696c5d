"""``chip_smoke.py`` kept alive between chip runs: the same phases at a
tiny width on the CPU with the kernels interpreted, its refusal to run
anywhere but on a TPU, and where the compile cache goes."""

import os

import pytest

import chip_smoke
from paddle_tpu.ops import common
from paddle_tpu.utils import runtime

# hidden stays a lane multiple so the tiny model takes the same resident
# kernel the full width does
TINY = chip_smoke.Width(vocab=200, embed=16, hidden=128, layers=2, batch=8,
                        seqlen=12, batches=2, passes=4, pool=8)


def test_phases_run_tiny_with_interpreted_kernels():
    with common.force_mode("interpret"):
        trained = chip_smoke.train_phase(TINY, expect_mosaic=False)
        # the LSTM is the step's one kernel entry: the optimizer's
        # update is plain jnp, left to the compiler's loop fusion
        assert trained["dispatch_tally"] == {"lstm": {"resident": 2}}
        assert trained["loss_last_pass"] < trained["loss_first_pass"]
        served = chip_smoke.serve_phase(TINY, trained)
        assert served["repeat_byte_equal"] and served["fatal"] is None
        assert {"b1_t12", "b4_t12"} <= set(served["bucket_hits"])
        meshed = chip_smoke.mesh_phase(TINY, trained, expect_mosaic=False)
        assert meshed["mesh"]["data"] == 4 and meshed["all_reduce_in_hlo"]
        # XLA cannot partition a Mosaic kernel: on the mesh the LSTM
        # kernels run per device on their own batch rows (batch_local)
        assert meshed["dispatch_tally"] == {"lstm": {"resident": 2}}


def test_real_entry_refuses_a_non_tpu_backend(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main()
    assert exc.value.code not in (0, None)
    assert "needs a TPU" in str(exc.value)
    assert capsys.readouterr().out == ""  # no result line


def test_real_entry_refuses_a_forced_kernel_path(capsys):
    with common.force_mode("interpret"), pytest.raises(SystemExit) as exc:
        chip_smoke.main()
    assert common.FORCE_ENV in str(exc.value)
    assert capsys.readouterr().out == ""


def test_mosaic_call_parser_reads_operand_shapes():
    # the line shape is the chip's (jax 0.9.0 / libtpu 0.0.34)
    hlo = ('  %c.1 = (f32[100,16,256]{2,1,0:T(8,128)}, f32[16,256]{1,0}) '
           'custom-call(%reshape.2, %copy-done), '
           'custom_call_target="tpu_custom_call", '
           'operand_layout_constraints={f32[100,16,1024]{2,1,0}, '
           'f32[256,1024]{1,0}}, frontend_attributes={kernel_metadata={}}\n'
           '  %other = f32[4] add(f32[4] %x, f32[4] %y)\n')
    n, shapes = chip_smoke._mosaic_calls(hlo)
    assert n == 1
    assert shapes == [["f32[100,16,1024]", "f32[256,1024]"]]


def test_compile_cache_dir_follows_env_else_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv(runtime.CACHE_ENV, raising=False)
    path, from_env = runtime.compile_cache_dir()
    checkout = os.path.dirname(os.path.abspath(chip_smoke.__file__))
    assert (path, from_env) == (os.path.join(checkout, ".jax_cache"), False)
    assert runtime.compile_cache_dir() == (path, False)  # nothing moves
    monkeypatch.setenv(runtime.CACHE_ENV, "/somewhere/else")
    assert runtime.compile_cache_dir() == ("/somewhere/else", True)


_TWO_NAMES = """
import re, sys
import jax, jax.numpy as jnp
from paddle_tpu.utils import runtime
runtime.enable_compile_cache()
from paddle_tpu.parallel.moe import swiglu
def f(x):
    with jax.named_scope(sys.argv[1]):
        return swiglu(jnp.sin(x), x, x, x)
text = jax.jit(f).lower(jnp.ones((64, 64))).compile().as_text()
print(sorted(set(re.findall(r'op_name="jit\\(f\\)/(\\w+)/', text))),
      re.findall(r'"([^"]*parallel/moe.py)"', text))
"""


def test_a_cached_program_is_not_served_under_another_scopes_names(tmp_path):
    """Two programs that differ only in a ``jax.named_scope`` are two
    entries of the persistent cache: the second process reads its own
    ``op_name`` paths in its compiled text, not the first's (JAX's key
    leaves names out unless asked; the benchmark reads device time by
    those paths, and a PR that only renames a scope would read the
    parent's)."""
    import subprocess
    import sys
    checkout = os.path.dirname(os.path.abspath(chip_smoke.__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=checkout,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    said = [subprocess.run([sys.executable, "-c", _TWO_NAMES, name], env=env,
                           capture_output=True, text=True, timeout=120,
                           check=True).stdout.strip()
            for name in ("first", "second", "first")]
    # and the call sites' files are written relative to the checkout, so
    # that another checkout of the same code finds these entries
    there = " ['paddle_tpu/parallel/moe.py']"
    assert said == ["['first']" + there, "['second']" + there,
                    "['first']" + there]
    assert len([f for f in os.listdir(tmp_path) if f.startswith("jit_f")]) == 2
