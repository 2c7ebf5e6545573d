"""CPU rehearsal of ``chip_smoke.py``: its phases at a reduced size with the
GC kernels in interpret mode, and its refusal to run without a TPU."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

jax.config.update("jax_platform_name", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from repro.configs import reduced_config  # noqa: E402
from repro.core.telemetry import GCConfig  # noqa: E402

INTERPRET = dict(use_kernel=True, kernel_interpret=True)
# phase B's shape of traffic on a pool of 48 four-token pages
TINY_PAGED = dict(num_seqs=8, num_pages=48, page_size=4, max_pages=8,
                  kv_heads=1, head_dim=8, lengths=(8, 24), lanes=2, hold=8,
                  check_every=4, seqs_per_pin=2)


def _env(**extra):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu", **extra)
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    return env


def test_import_touches_no_device():
    code = ("import chip_smoke\n"
            "from jax._src import xla_bridge as xb\n"
            "assert not xb._backends, xb._backends\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=_env(),
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr


def test_main_exits_nonzero_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_phase_serve_rehearsal():
    gc = GCConfig(policy="slrt", versions_per_slot=16, reader_lanes=8,
                  **INTERPRET)
    res = chip_smoke.phase_serve(reduced_config("gemma2-2b"), batch=2,
                                 prompt_len=16, steps=17, max_len=64, gc=gc,
                                 log=lambda s: None)
    assert res["pinned_readers"] == 3 and res["requests"] == 2


def test_phase_serve_reference_rehearsal():
    gc = GCConfig(policy="slrt", versions_per_slot=16, reader_lanes=8,
                  **INTERPRET)
    res = chip_smoke.phase_serve_reference(reduced_config("gemma2-2b"),
                                           gc=gc, log=lambda s: None)
    assert res["mismatches"] == 0 and res["tokens_compared"] > 0


def test_phase_paged_rehearsal():
    res = chip_smoke.phase_paged(dtype=jnp.bfloat16,
                                 gc=GCConfig(policy="slrt", **INTERPRET),
                                 log=lambda s: None, **TINY_PAGED)
    assert res["reclaiming_events"] >= 3
    assert res["pin_checks"] > 0 and res["pin_violations"] == 0


def test_phase_paged_catches_an_unpinned_reader(monkeypatch):
    """A reader that takes a timestamp without announcing it is not
    protected; the pinned-view check must see its view change."""
    from repro.serve.engine import PagedKVEngine
    monkeypatch.setattr(PagedKVEngine, "pin",
                        lambda self, lane: int(self.st.mv.now))
    monkeypatch.setattr(PagedKVEngine, "unpin", lambda self, lane: None)
    with pytest.raises(chip_smoke.Fail, match="changed under their pin"):
        chip_smoke.phase_paged(dtype=jnp.bfloat16,
                               gc=GCConfig(policy="slrt"),
                               log=lambda s: None,
                               **dict(TINY_PAGED, min_reclaiming=6))


def test_phase_four_chips_rehearsal():
    """The sharded phase on four virtual CPU devices: a real mesh, the ring
    all-reduce and the single-host replay of shard 0."""
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {REPO!r})
        import jax, jax.numpy as jnp
        import chip_smoke
        from repro.core.telemetry import GCConfig
        assert len(jax.devices()) == 4
        res = chip_smoke.phase_four_chips(
            dtype=jnp.bfloat16, stall_step=3, log=lambda s: None,
            gc=GCConfig(policy="slrt", use_kernel=True, kernel_interpret=True),
            **{TINY_PAGED!r})
        assert res["devices"] == 4 and res["replay"]["free_pages_match"]
        print("four chips OK", res["lwm_advances"], res["pin_checks"])
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=600,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert out.returncode == 0, f"{out.stdout}\n{out.stderr}"
    assert "four chips OK" in out.stdout
