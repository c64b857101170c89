"""Native dataio library: build, correctness vs numpy, fallback parity,
loader integration (SURVEY §2.4 native-components row)."""

import json
import os
import subprocess
import sys

import numpy
import pytest

from veles_tpu import native

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def lib_available():
    ok = native.available()
    if not ok:
        pytest.skip("g++ unavailable — native path untestable")
    return ok


class TestBuild:
    def test_builds_and_loads(self, lib_available):
        assert os.path.exists(os.path.join(
            os.path.dirname(native.__file__), "libdataio.so"))

    def test_makefile_builds_too(self, tmp_path):
        native_dir = os.path.dirname(os.path.abspath(native.__file__))
        result = subprocess.run(
            ["make", "-n", "-C", native_dir], capture_output=True, text=True)
        assert result.returncode == 0


class TestGatherConvert:
    def test_u8_matches_numpy(self, lib_available):
        r = numpy.random.RandomState(0)
        src = r.randint(0, 256, (100, 7, 5), dtype=numpy.uint8)
        idx = r.randint(0, 100, 32).astype(numpy.int32)
        out = native.gather_convert(src, idx, scale=1.0 / 127.5,
                                    offset=-1.0)
        expect = src[idx].astype(numpy.float32) / 127.5 - 1.0
        numpy.testing.assert_allclose(out, expect, rtol=1e-6, atol=1e-6)

    def test_f32_matches_numpy(self, lib_available):
        r = numpy.random.RandomState(1)
        src = r.randn(50, 12).astype(numpy.float32)
        idx = r.randint(0, 50, 20).astype(numpy.int32)
        numpy.testing.assert_array_equal(native.gather_convert(src, idx),
                                         src[idx])

    def test_memmap_source(self, lib_available, tmp_path):
        r = numpy.random.RandomState(2)
        data = r.randint(0, 256, (40, 6), dtype=numpy.uint8)
        path = str(tmp_path / "data.bin")
        data.tofile(path)
        mapped = numpy.memmap(path, numpy.uint8, "r", shape=(40, 6))
        idx = numpy.arange(0, 40, 2, dtype=numpy.int32)
        out = native.gather_convert(mapped, idx, scale=2.0, offset=1.0)
        numpy.testing.assert_allclose(
            out, mapped[idx].astype(numpy.float32) * 2.0 + 1.0)

    def test_labels_and_mean(self, lib_available):
        r = numpy.random.RandomState(3)
        labels = r.randint(0, 10, 100).astype(numpy.int32)
        idx = r.randint(0, 100, 30).astype(numpy.int32)
        numpy.testing.assert_array_equal(
            native.gather_labels(labels, idx), labels[idx])
        batch = r.randn(8, 5).astype(numpy.float32)
        mean = r.randn(5).astype(numpy.float32)
        expect = batch - mean
        out = native.subtract_mean(batch.copy(), mean)
        numpy.testing.assert_allclose(out, expect, rtol=1e-6)

    def test_broadcast_mean_keeps_numpy_semantics(self, lib_available):
        """A per-channel mean (not sample-shaped) must broadcast like
        numpy, not read out of bounds in the native kernel."""
        r = numpy.random.RandomState(4)
        batch = r.randn(4, 6, 6, 3).astype(numpy.float32)
        channel_mean = numpy.array([104.0, 117.0, 123.0], numpy.float32)
        expect = batch - channel_mean
        out = native.subtract_mean(batch.copy(), channel_mean)
        numpy.testing.assert_allclose(out, expect, rtol=1e-6)

    def test_strided_source_matches(self, lib_available):
        r = numpy.random.RandomState(5)
        full = r.randint(0, 256, (20, 4, 4, 4), dtype=numpy.uint8)
        view = full[:, :, :, :3]          # non-contiguous channel slice
        idx = numpy.arange(0, 20, 2, dtype=numpy.int32)
        out = native.gather_convert(view, idx, scale=2.0)
        numpy.testing.assert_allclose(
            out, view[idx].astype(numpy.float32) * 2.0)


class TestFallbackParity:
    def test_env_forced_fallback_matches(self, lib_available):
        """The numpy fallback must produce identical results (subprocess so
        the env var takes effect before first load)."""
        code = """
import os
os.environ["VELES_TPU_NO_NATIVE"] = "1"
import numpy
import sys
sys.path.insert(0, %r)
from veles_tpu import native
assert not native.available()
r = numpy.random.RandomState(0)
src = r.randint(0, 256, (100, 7, 5), dtype=numpy.uint8)
idx = r.randint(0, 100, 32).astype(numpy.int32)
out = native.gather_convert(src, idx, scale=1.0/127.5, offset=-1.0)
expect = src[idx].astype(numpy.float32) / 127.5 - 1.0
numpy.testing.assert_allclose(out, expect, rtol=1e-6, atol=1e-6)
print("fallback-ok")
"""
        repo = os.path.dirname(os.path.dirname(os.path.abspath(
            native.__file__)))
        result = subprocess.run(
            [sys.executable, "-c", code % repo], capture_output=True, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert "fallback-ok" in result.stdout, result.stderr


class TestLoaderIntegration:
    def test_records_loader_uses_native_path(self, lib_available, tmp_path):
        from veles_tpu.loader.records import write_records, RecordsLoader
        from veles_tpu.workflow import Workflow
        r = numpy.random.RandomState(0)
        data = r.randint(0, 256, (30, 4, 4, 3), dtype=numpy.uint8)
        labels = (numpy.arange(30) % 3).astype(numpy.int32)
        path = str(tmp_path / "set.rec")
        write_records(path, data, labels, [0, 10, 20])
        wf = Workflow(None, name="wf")
        loader = RecordsLoader(wf, path=path, minibatch_size=8,
                               name="loader")
        loader.initialize()
        loader.run()
        idx = numpy.asarray(loader.minibatch_indices.mem)
        expect = data[idx].astype(numpy.float32) / 127.5 - 1.0
        # the native kernel computes x*(1/127.5)-1 — one ulp of slack
        numpy.testing.assert_allclose(
            numpy.asarray(loader.minibatch_data.mem), expect,
            rtol=1e-6, atol=1e-6)
        numpy.testing.assert_array_equal(
            numpy.asarray(loader.minibatch_labels.mem), labels[idx])


class TestArtifactRunner:
    """The C++ PJRT standalone runner (libVeles parity, SURVEY §2.4):
    build, plugin loading, and bundle export are exercised everywhere;
    the full compile+execute leg needs a real device and is TPU-marked
    like the Pallas PRNG tests."""

    @pytest.fixture(scope="class")
    def runner_bin(self):
        import subprocess
        d = os.path.join(REPO, "veles_tpu", "native")
        subprocess.run(["make", "artifact_runner"], cwd=d, check=True,
                       stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        return os.path.join(d, "artifact_runner")

    def _plugin(self):
        plugin = native.find_pjrt_plugin()
        if plugin is None:
            pytest.skip("no PJRT plugin .so on this image")
        return plugin

    def test_selfcheck_loads_plugin(self, runner_bin):
        import subprocess
        out = subprocess.run([runner_bin, "--selfcheck", self._plugin()],
                             stdout=subprocess.PIPE, check=True,
                             timeout=120).stdout.decode()
        assert "SELFCHECK OK" in out
        assert "pjrt_api_version" in out

    def test_export_native_bundle(self, tmp_path):
        from veles_tpu import export, prng
        from veles_tpu.config import root
        prng.reset(); prng.seed_all(1)
        root.mnist.update({
            "loader": {"minibatch_size": 50, "n_train": 200,
                       "n_valid": 100},
            "decision": {"max_epochs": 1, "fail_iterations": 5},
            "layers": [
                {"type": "all2all_tanh", "output_sample_shape": 16,
                 "learning_rate": 0.03, "momentum": 0.9},
                {"type": "softmax", "output_sample_shape": 10,
                 "learning_rate": 0.03, "momentum": 0.9},
            ],
        })
        from veles_tpu.samples import mnist
        wf = mnist.train()
        bundle = export.export_native_bundle(wf, str(tmp_path / "nb"),
                                             batch=4)
        mlir = open(os.path.join(bundle, "program.mlir")).read()
        # weights are baked in: constants present, module well-formed
        assert "module" in mlir and "stablehlo" in mlir
        assert "4x784" in mlir        # static input shape in signature
        assert os.path.getsize(
            os.path.join(bundle, "compile_options.pb")) > 0
        assert open(os.path.join(bundle, "input.shape")).read() == "4 784"
        manifest = json.load(open(os.path.join(bundle, "manifest.json")))
        assert manifest["output_shape"] == [4, 10]

    def test_execute_on_device(self, runner_bin, tmp_path):
        import subprocess
        from veles_tpu.ops.pallas_kernels import on_tpu
        if not on_tpu():
            pytest.skip("full compile+execute needs a real PJRT device")
        from veles_tpu import export, prng
        from veles_tpu.config import root
        prng.reset(); prng.seed_all(1)
        root.mnist.update({
            "loader": {"minibatch_size": 50, "n_train": 200,
                       "n_valid": 100},
            "decision": {"max_epochs": 1, "fail_iterations": 5},
        })
        from veles_tpu.samples import mnist
        wf = mnist.train()
        bundle = export.export_native_bundle(wf, str(tmp_path / "nb"),
                                             batch=2)
        x = numpy.random.RandomState(0).uniform(
            -1, 1, (2, 784)).astype(numpy.float32)
        (tmp_path / "in.bin").write_bytes(x.tobytes())
        out = subprocess.run(
            [runner_bin, bundle, self._plugin(),
             str(tmp_path / "in.bin"), str(tmp_path / "out.bin")],
            stdout=subprocess.PIPE, check=True, timeout=600
        ).stdout.decode()
        assert "EXECUTE OK" in out
        got = numpy.frombuffer(
            (tmp_path / "out.bin").read_bytes(), numpy.float32
        ).reshape(2, 10)
        want = numpy.asarray(
            wf._fused_runner.eval_forward()(wf._fused_runner.state, x))
        numpy.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
