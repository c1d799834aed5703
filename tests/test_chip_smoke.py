"""chip_smoke.py wiring + the no-hidden-fallback contracts it relies on.

The smoke itself only means something on the chip; here its stages are
debugged at toy sizes on CPU (``--toy``, stamped as such), and the
pieces that make a chip failure LOUD are unit-tested: compile errors
propagate out of the AOT ladder and fail the deploy, the compile cache
resolves to one fixed place, the native loader ignores a stale build.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run_smoke(args, tmp_path, **env_overrides):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # one CPU device, like one chip
    env.update(JAX_PLATFORMS="cpu", **env_overrides)
    return subprocess.run(
        [sys.executable, SMOKE, "--out", str(tmp_path / "out"),
         "--work", str(tmp_path / "work"), *args],
        env=env, capture_output=True, text=True, timeout=600)


class TestChipSmoke:
    def test_refuses_cpu_and_names_it(self, tmp_path):
        """Without --toy the smoke is chip-or-fail: on a CPU platform it
        exits non-zero, prints no result line, and says what it found."""
        proc = _run_smoke([], tmp_path)
        assert proc.returncode != 0
        assert proc.stdout.strip() == ""
        assert "platform is 'cpu', not 'tpu'" in proc.stderr

    def test_toy_run_passes_every_stage_on_cpu(self, tmp_path):
        cache = tmp_path / "jax_cache"
        proc = _run_smoke(
            ["--toy"], tmp_path,
            # conftest turns the persistent cache off for the suite;
            # the smoke's children are where it is exercised
            JAX_ENABLE_COMPILATION_CACHE="true",
            JAX_COMPILATION_CACHE_DIR=str(cache))
        assert proc.returncode == 0, proc.stderr[-4000:]
        report, verdict = proc.stdout.strip().splitlines()
        # the last line is the verdict and nothing but: "ok" and
        # "device" (plus the toy stamp, which a chip run never carries)
        assert json.loads(verdict) == {
            "ok": True, "toy": True,
            "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
        result = json.loads(report)
        assert result["ok"] is True and result["toy"] is True
        assert result["device"] == {"platform": "cpu", "kind": "cpu",
                                    "count": 1}
        assert list(result["stageSeconds"]) == [
            "probe", "load", "train", "serve", "foldin", "stop",
            "redeploy"]
        assert result["cacheDir"] == str(cache)
        assert result["nativeLoaded"] == {"jsonl_codec": True,
                                          "ingest_kernels": True}
        train, serve = result["train"], result["serve"]
        assert train["trainedPairs"] == result["uniquePairs"]
        assert train["losses"][-1] < train["losses"][0]
        assert serve["ladder"]["fallback"] == 0
        assert serve["ladder"]["compiled"] == serve["ladder"]["planned"]
        assert serve["dispatch"]["aot"]["missJit"] == 0
        assert serve["queries"]["minShared"] >= 9
        foldin = result["foldin"]
        assert foldin["newUsers"] == 1
        # the new user grew the store; its ladder was compiled before
        # it was published, so no dispatch after it compiled either
        assert foldin["userCapacity"] > result["shape"]["n_users"]
        assert foldin["dispatch"]["aot"]["missJit"] == 0
        assert foldin["dispatch"]["aot"]["hit"] \
            > serve["dispatch"]["aot"]["hit"]
        # the serving ladder's sub-second programs are stored, and the
        # second deploy compiled nothing new
        assert serve["cacheEntriesAfter"] > serve["cacheEntriesBefore"]
        redeploy = result["redeploy"]
        assert redeploy["cacheEntriesAfter"] \
            == redeploy["cacheEntriesBefore"] > 0
        assert not (tmp_path / "work").exists()  # scratch removed

    def test_events_floor(self, tmp_path):
        proc = _run_smoke(["--events", "1999999"], tmp_path)
        assert proc.returncode != 0
        assert "below the floor" in proc.stderr


class TestCompileCache:
    PROBE = ("from predictionio_tpu.utils import compile_cache\n"
             "import jax\n"
             "d = compile_cache.configure()\n"
             "print(d)\n"
             "print(jax.config.jax_compilation_cache_dir)\n"
             "print(jax.config.jax_persistent_cache_min_compile_time_secs)")

    def _probe(self, cwd, **env_overrides):
        env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        env.update(env_overrides)
        out = subprocess.run([sys.executable, "-c", self.PROBE], cwd=cwd,
                             env=env, capture_output=True, text=True,
                             timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        return out.stdout.strip().splitlines()

    def test_env_dir_is_left_alone(self, tmp_path):
        """JAX_COMPILATION_CACHE_DIR set: no cache path is set in code
        (jax's own reading of the variable is what the config holds)."""
        want = str(tmp_path / "elsewhere")
        resolved, configured, min_secs = self._probe(
            str(tmp_path), JAX_COMPILATION_CACHE_DIR=want)
        assert resolved == want and configured == want
        assert float(min_secs) == 0.0  # sub-second programs get stored

    def test_unset_resolves_one_fixed_dir_in_the_checkout(self, tmp_path):
        a = self._probe(str(tmp_path))
        b = self._probe(REPO)
        assert a == b
        assert a[0] == a[1] == os.path.join(REPO, ".jax_cache")


class _Refused(Exception):
    pass


class TestCompileErrorsPropagate:
    def test_lower_compile_lets_the_compiler_error_out(self):
        from predictionio_tpu.ops.aot import lower_compile

        class Jitted:
            def lower(self, *a, **k):
                raise _Refused("RESOURCE_EXHAUSTED: space=vmem")

        with pytest.raises(_Refused, match="space=vmem"):
            lower_compile(Jitted())

    def test_ladder_compile_failure_fails_precompile(self, monkeypatch):
        import numpy as np

        from predictionio_tpu.ops import serving

        rng = np.random.default_rng(0)
        srv = serving.DeviceTopK(
            rng.normal(size=(8, 4)).astype(np.float32),
            rng.normal(size=(12, 4)).astype(np.float32))

        def refuse(*a, **k):
            raise _Refused("Mosaic failed to compile TPU kernel")

        monkeypatch.setattr(serving, "lower_compile", refuse)
        try:
            with pytest.raises(_Refused, match="Mosaic"):
                srv.warmup()
        finally:
            srv.close()

    def test_warm_up_fails_the_deploy(self):
        """A device-served model whose ladder does not compile fails
        ``warm_up`` — and with it ``pio deploy`` — with the compiler's
        message instead of logging "non-fatal"."""
        import importlib

        # (the package re-exports a create_server FUNCTION of the same
        # name, so the module has to be asked for explicitly)
        create_server = importlib.import_module(
            "predictionio_tpu.workflow.create_server")

        class Algo:
            query_class = None

            def warmup_base(self, model):
                raise _Refused("Mosaic failed to compile TPU kernel")

        class HooklessModel:
            def device_server(self):
                raise _Refused("ladder refused")

        dep = create_server.Deployment.__new__(create_server.Deployment)
        dep.algorithms, dep.models = [Algo()], [object()]
        with pytest.raises(_Refused, match="Mosaic"):
            create_server.warm_up(dep)
        dep.algorithms, dep.models = [object()], [HooklessModel()]
        with pytest.raises(_Refused, match="ladder refused"):
            create_server.warm_up(dep)


class TestNativeLoader:
    def test_stale_build_is_ignored(self, tmp_path, monkeypatch):
        """Only ``src/*.cpp`` decides what is loaded: a library left in
        ``_build/`` under the old mtime-keyed name — newer than the
        source, built from something else — is never picked up."""
        from predictionio_tpu import native

        build = tmp_path / "_build"
        build.mkdir()
        stale = build / "libjsonl_codec.so"
        stale.write_bytes(b"not a library")
        monkeypatch.setattr(native, "_BUILD_DIR", str(build))
        monkeypatch.setattr(native, "_cache", {})
        lib = native.load("jsonl_codec")
        assert lib is not None  # built fresh from source, loads
        built = sorted(p.name for p in build.glob("*.so"))
        assert len(built) == 2 and "libjsonl_codec.so" in built
        (fresh,) = [n for n in built if n != "libjsonl_codec.so"]
        import hashlib

        with open(os.path.join(native._SRC_DIR, "jsonl_codec.cpp"),
                  "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
        assert fresh == f"libjsonl_codec-{digest}.so"
        assert stale.read_bytes() == b"not a library"  # untouched
