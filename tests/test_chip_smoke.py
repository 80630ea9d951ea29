"""chip_smoke.py: its guards, and its phases at a tiny size on the CPU.

The script runs at full size only on a TPU.  Here the phases are called
directly at tiny sizes (the Pallas kernel interprets on the CPU backend),
which rehearses their control flow and checks without the chip.
"""

import os
import shutil
import subprocess
import sys
import textwrap

import jax
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

import chip_smoke  # noqa: E402

TINY = chip_smoke.Sizes(
    whatif_queries=3000, p=8, lam=(20.0, 80.0), cpu=(1.0, 4.0),
    disk=(4.0,), hit=(0.18,), grid_queries=2000, chunk=512,
    kernel_rows=16, kernel_check_rows=3, analytic_lam=4, analytic_hit=4)


def _run_script(cwd, timeout=300):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


def _no_result_line(stdout: str) -> bool:
    return '"ok"' not in stdout


def test_chip_smoke_refuses_cpu():
    r = _run_script(_ROOT)
    assert r.returncode != 0, r.stdout
    assert _no_result_line(r.stdout)
    assert "no TPU" in r.stderr


def test_chip_smoke_alone_fails(tmp_path):
    shutil.copy(os.path.join(_ROOT, "chip_smoke.py"), tmp_path)
    r = _run_script(tmp_path)
    assert r.returncode != 0, r.stdout
    assert _no_result_line(r.stdout)


def test_chip_smoke_one_chip_phases_tiny(monkeypatch, capsys):
    lowered = []
    # the CPU backend interprets the kernel, so its stream program holds
    # no Mosaic call; record that the lowering was inspected instead
    monkeypatch.setattr(chip_smoke, "check_kernel_compiled",
                        lowered.append)
    chip_smoke.run_one_chip(TINY, jax.random.PRNGKey(0))
    out = capsys.readouterr().out
    assert len(lowered) == 1 and "_simulate_stream" in lowered[0]
    assert "what-if: 4 replicas x 100 servers" in out
    assert "pallas vs xla" in out
    assert "kernel maxplus_segment_scan" in out


def test_chip_smoke_four_chip_phases_tiny():
    code = ("import os\n"
            "os.environ['XLA_FLAGS'] = "
            "'--xla_force_host_platform_device_count=4'\n"
            + textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {_ROOT!r})
        import jax
        import chip_smoke
        tiny = chip_smoke.Sizes(**{TINY.__dict__!r})
        # XLA:CPU compiles the broadcast (unsharded) and the flat
        # (sharded) Eq 7/8 programs with different vector code, which
        # rounds a few values apart; the chip is held to exact equality
        chip_smoke.ANALYTIC_RTOL = 1e-5
        chip_smoke.run_four_chips(tiny, jax.random.PRNGKey(0))
        print('OK four')
    """))
    env = dict(os.environ, PYTHONPATH=os.path.join(_ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "every shard matches its direct rebuild" in r.stdout
    assert "equal the unsharded surfaces" in r.stdout
    assert "OK four" in r.stdout


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/cache"])
def test_compile_cache_dir(monkeypatch, env_dir):
    from repro import compile_cache

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    compile_cache.enable_compile_cache()
    if env_dir is None:
        assert calls == [("jax_compilation_cache_dir",
                          os.path.join(_ROOT, ".jax_cache"))]
    else:
        assert calls == []
