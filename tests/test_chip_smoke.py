"""CPU rehearsal of ``chip_smoke.py``: its phase functions run end to end at
a tiny genome size with the Pallas kernels in interpret mode, so the
script's control flow and checks are exercised on every test run; and the
script itself refuses to run anywhere but on a TPU."""

import contextlib
import io
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def rehearsal():
    """Both one-chip phases at an 8 kb genome (they share the compiles),
    with the printed lines captured."""
    clock = chip_smoke.CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            parity = chip_smoke.phase_parity(clock, seed=0, genome_bp=8_000)
            real = chip_smoke.phase_real_size(clock, seed=0, genome_bp=8_000)
    finally:
        jax.monitoring.unregister_event_duration_listener(clock)
    return parity, real, buf.getvalue()


def test_chip_smoke_parity_phase_rehearses_on_cpu(rehearsal):
    parity, _, out = rehearsal
    assert parity == []
    assert ("[parity] bit_identical r_graph=True s_graph=True contigs=True "
            "polished_contigs=True") in out
    assert "[parity/pallas] stage=Alignment" in out and "compile_s=" in out
    assert '"xdrop_extend": "pallas-interpret"' in out


def test_chip_smoke_real_size_phase_rehearses_on_cpu(rehearsal):
    _, real, out = rehearsal
    assert real == []
    assert "[real] genome cut from 4641652 to 8000 bp" in out
    assert "[real] cc_labels impl=pallas-interpret" in out
    assert "labels_equal=True" in out


def test_chip_smoke_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_chip_smoke_alone_fails(tmp_path):
    """Copied into a directory without the repo it exits non-zero and
    prints no result."""
    script = tmp_path / "chip_smoke.py"
    script.write_text(open(os.path.join(ROOT, "chip_smoke.py")).read())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, str(script)], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
