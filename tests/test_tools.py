"""Tools tests: AOT, native library, profiler merge.

Mirrors the reference's AOT path (compile_aot.py + triton_aot_runtime)
and group_profile merge (utils.py:282-502).
"""

import gzip
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_distributed_tpu.tools import (
    AotLibrary,
    TokenDataset,
    aot_compile,
    aot_load,
    artifact_read,
    artifact_write,
    group_profile,
    merge_chrome_traces,
    moe_align_block_size_host,
)

#: tier-1 fast subset (ci/fast.sh): AOT metadata + profiler merge, no collectives
pytestmark = pytest.mark.fast


class TestAot:
    def test_roundtrip(self, tmp_path):
        def f(a, b):
            return a @ b + 1

        args = (jnp.ones((16, 32)), jnp.ones((32, 8)))
        p = aot_compile(f, args, name="mm", cache_dir=tmp_path)
        g = aot_load(p)
        np.testing.assert_allclose(np.asarray(g(*args)), np.asarray(f(*args)))

    def test_library_dispatch_and_disk_reload(self, tmp_path):
        def f(a):
            return a * 2

        lib = AotLibrary(f, name="dbl", cache_dir=tmp_path)
        lib.compile(jnp.ones((8, 8)))
        # a fresh library instance must find the artifact on disk
        lib2 = AotLibrary(f, name="dbl", cache_dir=tmp_path)
        out = lib2(jnp.ones((8, 8)))
        np.testing.assert_allclose(np.asarray(out), 2.0)
        # unseen shape falls back to jit
        out2 = lib2(jnp.ones((4, 4)))
        np.testing.assert_allclose(np.asarray(out2), 2.0)


class TestNative:
    def test_artifact_roundtrip(self, tmp_path):
        blob = bytes(range(256)) * 100
        path = str(tmp_path / "a.art")
        artifact_write(path, blob)
        assert artifact_read(path) == blob

    def test_artifact_corruption_detected(self, tmp_path):
        from triton_distributed_tpu.tools.native import native_lib

        if native_lib() is None:
            pytest.skip("native library unavailable")
        path = str(tmp_path / "a.art")
        artifact_write(path, b"payload-bytes-here")
        raw = bytearray(pathlib.Path(path).read_bytes())
        raw[20] ^= 0xFF                       # flip a payload byte
        pathlib.Path(path).write_bytes(raw)
        with pytest.raises(IOError):
            artifact_read(path)

    def test_artifact_truncation_detected_python_path(self, tmp_path, monkeypatch):
        """A framed artifact cut short must raise, not come back as
        garbage raw bytes misread as a legacy file (ADVICE r1)."""
        from triton_distributed_tpu.tools import native as nat

        path = str(tmp_path / "a.art")
        artifact_write(path, b"payload-bytes-here" * 10)
        raw = pathlib.Path(path).read_bytes()
        pathlib.Path(path).write_bytes(raw[: len(raw) // 2])
        monkeypatch.setattr(nat, "_lib_cache", [None])  # pure-python reader
        with pytest.raises(IOError):
            artifact_read(path)

    def test_artifact_corruption_detected_python_path(self, tmp_path, monkeypatch):
        from triton_distributed_tpu.tools import native as nat

        path = str(tmp_path / "a.art")
        artifact_write(path, b"payload-bytes-here" * 10)
        raw = bytearray(pathlib.Path(path).read_bytes())
        raw[20] ^= 0xFF
        pathlib.Path(path).write_bytes(bytes(raw))
        monkeypatch.setattr(nat, "_lib_cache", [None])
        with pytest.raises(IOError):
            artifact_read(path)

    def test_artifact_cross_environment(self, tmp_path, monkeypatch):
        """Native-written artifacts must be readable by the pure-python
        path and vice versa (same framed on-disk format)."""
        from triton_distributed_tpu.tools import native as nat

        blob = b"cross-env-payload" * 50
        p_native = str(tmp_path / "n.art")
        artifact_write(p_native, blob)
        # force the fallback reader
        monkeypatch.setattr(nat, "_lib_cache", [None])
        assert artifact_read(p_native) == blob
        p_py = str(tmp_path / "p.art")
        artifact_write(p_py, blob)              # python writer
        monkeypatch.setattr(nat, "_lib_cache", [])
        assert artifact_read(p_py) == blob      # native reader (if built)

    def test_moe_align_rejects_bad_ids(self):
        ids = np.array([[0, 16]], np.int32)     # 16 == num_experts
        with pytest.raises(ValueError, match="out of range"):
            moe_align_block_size_host(ids, 16, 8)

    def test_moe_align_matches_jax(self):
        from triton_distributed_tpu.kernels import moe_utils as mu

        ids = np.random.default_rng(0).integers(0, 16, (64, 2)).astype(np.int32)
        sti_n, be_n, spl_n = moe_align_block_size_host(ids, 16, 8)
        sti_j, be_j, spl_j = mu.moe_align_block_size(jnp.asarray(ids), 16, 8)
        np.testing.assert_array_equal(sti_n, np.asarray(sti_j))
        np.testing.assert_array_equal(be_n, np.asarray(be_j))
        np.testing.assert_array_equal(spl_n, np.asarray(spl_j))

    def test_token_dataset(self, tmp_path):
        toks = np.arange(5000, dtype=np.uint32)
        path = tmp_path / "toks.bin"
        toks.tofile(path)
        ds = TokenDataset(str(path))
        assert len(ds) == 5000
        b = ds.sample(4, 64, seed=7)
        assert b.shape == (4, 65)
        for row in b:                          # contiguous windows
            np.testing.assert_array_equal(
                row, np.arange(row[0], row[0] + 65, dtype=np.uint32)
            )
        np.testing.assert_array_equal(b, ds.sample(4, 64, seed=7))
        ds.close()


class TestProfile:
    def test_merge_remaps_pids(self, tmp_path):
        for i in range(2):
            sub = tmp_path / f"process-{i}" / "plugins" / "profile"
            sub.mkdir(parents=True)
            with gzip.open(sub / "host.trace.json.gz", "wt") as f:
                json.dump(
                    {"traceEvents": [{"pid": 1, "tid": 1, "name": f"op{i}"}]}, f
                )
        out = merge_chrome_traces(tmp_path)
        ev = json.load(gzip.open(out, "rt"))["traceEvents"]
        assert sorted(e["pid"] for e in ev) == [1, 100000001]

    def test_merge_empty_returns_none(self, tmp_path):
        assert merge_chrome_traces(tmp_path) is None

    def test_merge_refuses_partial_multiprocess(self, tmp_path, monkeypatch):
        """On a multi-process run, a merge that can only see the local
        host's traces must refuse loudly, not silently produce a
        partial timeline (VERDICT r3 weak #6)."""
        import jax

        sub = tmp_path / "process-0" / "plugins" / "profile"
        sub.mkdir(parents=True)
        with gzip.open(sub / "host.trace.json.gz", "wt") as f:
            json.dump({"traceEvents": [{"pid": 1, "name": "op"}]}, f)
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        with pytest.raises(RuntimeError, match="gather_traces"):
            merge_chrome_traces(tmp_path)

    def test_gather_traces_single_process_noop(self, tmp_path):
        from triton_distributed_tpu.tools import gather_traces

        assert gather_traces(tmp_path) == pathlib.Path(tmp_path)

    def test_group_profile_writes(self, tmp_path):
        with group_profile(tmp_path):
            jnp.dot(jnp.ones((32, 32)), jnp.ones((32, 32))).block_until_ready()
        assert list(pathlib.Path(tmp_path).rglob("*"))

    def test_group_profile_runs_without_the_python_tracer(
            self, tmp_path, monkeypatch):
        """The program opens its own host spans; the profiler's Python
        tracer (on by default) costs a serving step 1.4-1.9 ms."""
        import contextlib

        seen = {}

        @contextlib.contextmanager
        def trace(log_dir, **kw):
            seen.update(kw, log_dir=log_dir)
            yield

        monkeypatch.setattr(jax.profiler, "trace", trace)
        with group_profile(tmp_path) as where:
            pass
        assert seen["log_dir"] == str(where)
        assert seen["profiler_options"].python_tracer_level == 0
        assert seen["profiler_options"].host_tracer_level > 0


class TestCheckpoint:
    def test_roundtrip_with_resharding(self, mesh8, tmp_path):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from triton_distributed_tpu.tools import (
            restore_checkpoint,
            save_checkpoint,
        )

        params = {
            "w": jax.device_put(
                jnp.arange(64.0).reshape(8, 8),
                NamedSharding(mesh8, P("x", None)),
            ),
            "b": jnp.zeros((4,)),
            "nested": [jnp.ones((2, 2)), jnp.full((3,), 7)],
        }
        path = tmp_path / "ckpt"
        save_checkpoint(path, params)
        # restore onto a DIFFERENT sharding for w
        like = dict(params)
        like["w"] = jax.device_put(
            jnp.zeros((8, 8)), NamedSharding(mesh8, P(None, "x"))
        )
        out = restore_checkpoint(path, like)
        np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(params["w"]))
        assert out["w"].sharding.spec == P(None, "x")
        np.testing.assert_array_equal(np.asarray(out["nested"][1]), 7)

    def test_shape_mismatch_raises(self, tmp_path):
        from triton_distributed_tpu.tools import (
            restore_checkpoint,
            save_checkpoint,
        )

        save_checkpoint(tmp_path / "c", {"w": jnp.zeros((4,))})
        with pytest.raises(ValueError, match="shape mismatch"):
            restore_checkpoint(tmp_path / "c", {"w": jnp.zeros((5,))})

    def test_manager_retention_and_latest(self, tmp_path):
        from triton_distributed_tpu.tools import CheckpointManager

        mgr = CheckpointManager(tmp_path, keep=2)
        assert mgr.latest_step() is None
        assert mgr.restore({"w": jnp.zeros((2,))}) is None
        for s in (1, 5, 9):
            mgr.save(s, {"w": jnp.full((2,), float(s))})
        assert mgr.latest_step() == 9
        assert sorted(p.name for p in tmp_path.iterdir()) == ["step_5", "step_9"]
        out = mgr.restore({"w": jnp.zeros((2,))})
        np.testing.assert_allclose(np.asarray(out["w"]), 9.0)
        out5 = mgr.restore({"w": jnp.zeros((2,))}, step=5)
        np.testing.assert_allclose(np.asarray(out5["w"]), 5.0)

    def test_structure_mismatch_raises(self, tmp_path):
        from triton_distributed_tpu.tools import (
            restore_checkpoint,
            save_checkpoint,
        )

        save_checkpoint(tmp_path / "c", {"a": jnp.zeros((4,)), "b": jnp.ones((4,))})
        with pytest.raises(ValueError, match="tree structure"):
            restore_checkpoint(
                tmp_path / "c", {"a": jnp.zeros((4,)), "c": jnp.ones((4,))}
            )


def test_compile_aot_cli_roundtrip(tmp_path):
    """The AOT CLI (≡ reference compile_aot.py + gen_aot_code.sh) builds
    artifacts a fresh library with the same hyperparameters loads without
    a jit fallback."""
    import jax
    import jax.numpy as jnp

    from triton_distributed_tpu.kernels.flash_decode import (
        gqa_fwd_batch_decode_aot,
    )
    from triton_distributed_tpu.tools.compile_aot import main

    rc = main([
        "--cache-dir", str(tmp_path), "--batch", "2", "--q-heads", "8",
        "--kv-heads", "2", "--head-dim", "128", "--seq", "256",
        "--block-k", "128", "--dtype", "float32",
    ])
    assert rc == 0
    lib = gqa_fwd_batch_decode_aot(
        block_k=128, kv_layout="bhsd", cache_dir=tmp_path
    )
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 128), jnp.float32)
    kv = jax.random.normal(jax.random.PRNGKey(1), (2, 2, 256, 128), jnp.float32)
    out, _ = lib(q, kv, kv, jnp.array([200, 50], jnp.int32))
    assert lib.stats == {"artifact_loads": 1, "jit_fallbacks": 0}
    assert out.shape == (2, 8, 128)


def test_generate_cli(capsys):
    """The serving CLI: a batch through ``ServingEngine`` on a tiny
    preset (the L7 surface a user drives; tutorial 13 is the library
    version)."""
    from triton_distributed_tpu.tools.generate import main

    main(["--preset", "tiny", "--batch", "2", "--prompt-len", "8",
          "--steps", "2"])
    out = capsys.readouterr().out
    assert "decode:" in out and "sample completion ids:" in out


def test_generate_cli_unknown_preset():
    import pytest

    from triton_distributed_tpu.tools.generate import main

    with pytest.raises(SystemExit, match="unknown preset"):
        main(["--preset", "nope"])
