"""The driver's two entry points (``__graft_entry__.py``) stay runnable
on the 8-device CPU mesh: ``entry()`` is ``Transformer.forward``,
``dryrun_multichip`` trains one step and then serves the same weights
through ``Transformer.serving_step``."""

import pathlib
import sys

import jax

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import __graft_entry__ as graft  # noqa: E402


def test_entry_lowers():
    fn, args = graft.entry()
    lowered = jax.jit(fn).lower(*args)
    assert "stablehlo" in lowered.as_text()
    out = jax.eval_shape(fn, *args)
    assert out.shape == (2 * 32, 128)


def test_dryrun_multichip_serves(capsys):
    """dp×tp = 2×4: the train step, the shard guards over the first
    serving step's compiled program, every request served, and the
    fused-LL transport on two devices equal to the XLA transport."""
    graft.dryrun_multichip(8)
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert line.startswith("dryrun_multichip OK: dp×tp=(2,4)")
    assert "serving steps OK" in line and "fused-LL serving steps" in line
