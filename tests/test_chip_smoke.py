"""chip_smoke.py: it refuses to run without a TPU, and its phases pass at
smoke size here (bf16 weights, like the chip run; the four-chip phase on
four host-platform devices in a subprocess)."""
import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap

import jax

from repro.configs import registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = dict(seed=0, slots=2, max_context=64, max_new=4, decode_steps=2,
             per_instance=2, prompt_lo=20, prompt_hi=40, logits_len=16)


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg():
    return registry.get_smoke_config("qwen1.5-0.5b").with_(
        dtype="bfloat16", param_dtype="bfloat16")


def test_refuses_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert _chip_smoke().main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_fails_without_the_repo(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_one_chip_phase_at_smoke_size(capsys):
    _chip_smoke().one_chip(_cfg().with_(num_instances=2),
                           jax.random.PRNGKey(0), **SMOKE)
    out = capsys.readouterr().out
    assert "all ok" in out and out.count("reference logits rel err") == 2
    assert out.count("served first-token logits vs f32 reference") == 2


def test_four_chip_phase_on_host_devices():
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys
        sys.path.insert(0, {REPO!r})
        import jax
        import chip_smoke as cs
        from repro.configs import registry
        cfg = registry.get_smoke_config("qwen1.5-0.5b").with_(
            dtype="bfloat16", param_dtype="bfloat16")
        cs.four_chips(cfg, jax.random.PRNGKey(0), jax.devices(),
                      m_small=2, m_mesh=8, **{SMOKE!r})
        print("four-chip phase OK")
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=REPO, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "2 per chip" in r.stdout and "four-chip phase OK" in r.stdout
    assert r.stdout.count("served first-token logits rel err") == 2
