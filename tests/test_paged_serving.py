"""The donate-and-thread runner of ``bench.py`` (the repository root's
kernel-alone timer file; ``_make_donating_runner``, which ``bench_loop``
uses under ``donate_idx``): the LL persistent-workspace contract its
timing loops rest on. This is the runner's only guard — nothing else
imports ``bench.py``, and no tier-1 test or benchmark cell runs its
timers — so a change to the runner that hands a step fresh buffers
fails here and nowhere else."""

import jax.numpy as jnp


class TestDonatingRunner:
    def test_workspace_buffer_identity(self):
        """The bench's donate-and-thread runner must keep the SAME
        physical workspace buffers across invocations (the LL
        persistent-workspace contract, VERDICT r4 #8)."""
        import sys

        sys.path.insert(0, ".")
        from bench import _make_donating_runner

        x = jnp.ones((8,), jnp.float32)
        ws = jnp.zeros((128,), jnp.float32)

        def step(state, s):
            x, ws = state
            ws = ws + 1.0
            return (x, ws), s + jnp.sum(x) + ws[0]

        call = _make_donating_runner(step, (x, ws), 4, 1)
        d1, s1 = call(ws)
        p1 = d1.unsafe_buffer_pointer()
        d2, s2 = call(d1)
        p2 = d2.unsafe_buffer_pointer()
        assert p1 == p2, "workspace buffer was reallocated across invocations"
        # and the carry really threaded: 4 iters per call, ws grew by 8
        assert float(d2[0]) == 8.0
        assert s2 > s1
