"""HLO analyzer + dry-run artifact integrity tests (fast, 1-device)."""
import glob
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.launch import hlo_analyzer as H

ART = os.path.join(os.path.dirname(__file__), "..", "benchmarks", "artifacts")


class TestHloAnalyzer:
    def test_scan_trip_count_flops(self):
        def scanned(x, w):
            def body(c, wi):
                return jnp.tanh(c @ wi), None

            out, _ = jax.lax.scan(body, x, w)
            return out

        x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
        w = jax.ShapeDtypeStruct((8, 256, 256), jnp.float32)
        txt = jax.jit(scanned).lower(x, w).compile().as_text()
        got = H.analyze(txt)["flops"]
        assert got == 8 * 2 * 128 * 256 * 256  # loop-aware, exact

    def test_nested_scan(self):
        def nested(x, w):
            def outer(c, wo):
                def inner(c2, wi):
                    return c2 @ wi, None

                c2, _ = jax.lax.scan(inner, c, wo)
                return c2, None

            out, _ = jax.lax.scan(outer, x, w)
            return out

        x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        w = jax.ShapeDtypeStruct((3, 4, 64, 64), jnp.float32)
        txt = jax.jit(nested).lower(x, w).compile().as_text()
        got = H.analyze(txt)["flops"]
        assert got == 3 * 4 * 2 * 64 * 64 * 64

    def test_collective_bytes_psum(self):
        from jax.sharding import Mesh, PartitionSpec as P

        from jax import shard_map

        mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("data",))

        def f(x):
            return shard_map(
                lambda y: jax.lax.psum(y, "data"), mesh=mesh,
                in_specs=P(None), out_specs=P(None),
            )(x)

        x = jax.ShapeDtypeStruct((1024,), jnp.float32)
        txt = jax.jit(f).lower(x).compile().as_text()
        coll = H.analyze(txt)["collectives"]
        assert coll["all-reduce"] == 4096  # 1024 f32 result bytes
        assert coll["total"] == 4096

    def test_dus_counts_update_not_buffer(self):
        def f(buf, upd):
            def body(b, u):
                b = jax.lax.dynamic_update_slice(b, u, (jnp.int32(0), jnp.int32(0)))
                return b, None

            out, _ = jax.lax.scan(body, buf, upd)
            return out

        buf = jax.ShapeDtypeStruct((4096, 128), jnp.float32)
        upd = jax.ShapeDtypeStruct((16, 8, 128), jnp.float32)
        txt = jax.jit(f).lower(buf, upd).compile().as_text()
        got = H.analyze(txt)["hbm_bytes"]
        # 16 iterations x ~2 x (8*128*4 bytes) update traffic, NOT 16 x 2 MB
        assert got < 16 * 4096 * 128 * 4 / 4, got


class TestDryrunArtifacts:
    def test_all_baseline_cells_ok(self):
        files = [
            f
            for f in glob.glob(os.path.join(ART, "dryrun_*.json"))
            if json.load(open(f)).get("tag", "") == ""
        ]
        if not files:
            pytest.skip("no dry-run artifacts present")
        assert len(files) >= 64, f"expected 64 baseline cells, found {len(files)}"
        bad = []
        for f in files:
            d = json.load(open(f))
            if d.get("status") != "ok":
                bad.append((d["arch"], d["shape"], d["mesh"], d.get("error")))
        assert not bad, bad

    def test_roofline_terms_present_and_positive(self):
        files = glob.glob(os.path.join(ART, "dryrun_single_*train_4k.json"))
        files = [f for f in files if json.load(open(f)).get("tag", "") == ""]
        if not files:
            pytest.skip("no artifacts")
        for f in files:
            d = json.load(open(f))
            r = d["roofline"]
            assert r["compute_s"] > 0, d["arch"]
            assert r["memory_s"] > 0
            assert r["dominant"] in ("compute", "memory", "collective")
            assert 0 < d["model_flops_ratio"] < 10

    def test_multi_pod_cells_exist(self):
        files = [
            f
            for f in glob.glob(os.path.join(ART, "dryrun_multi_*.json"))
            if json.load(open(f)).get("tag", "") == ""
        ]
        if not files:
            pytest.skip("no artifacts")
        assert len(files) >= 32
        for f in files:
            d = json.load(open(f))
            assert d["n_devices"] == 512
            assert d["status"] == "ok"
