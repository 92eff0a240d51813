"""The device engines run on a TPU, or on the CPU only when the process
is pinned to it; the compile cache sits at a fixed path."""

import pytest

import jax

from seaweedfs_tpu.ops import device


def test_pinned_process_gets_the_cpu():
    assert device.pinned_to_cpu()
    assert device.device_backend() == "cpu"


@pytest.fixture
def unpinned(monkeypatch):
    monkeypatch.setattr(device, "pinned_to_cpu", lambda: False)


def test_unpinned_cpu_is_refused(unpinned):
    with pytest.raises(device.NoTpuError, match="JAX_PLATFORMS=cpu"):
        device.device_backend()


@pytest.mark.parametrize("engine", ["tpu-engine", "stream-device",
                                    "stream-mesh", "mesh-engine"])
def test_device_engines_refuse_to_start_without_tpu(unpinned, engine):
    from seaweedfs_tpu.ec.codec import MeshEngine
    from seaweedfs_tpu.ec.streaming import StreamingEncoder
    from seaweedfs_tpu.ops.gf_matmul import TpuEngine

    make = {"tpu-engine": TpuEngine,
            "stream-device": lambda: StreamingEncoder(engine="device"),
            "stream-mesh": lambda: StreamingEncoder(engine="mesh"),
            "mesh-engine": MeshEngine}[engine]
    with pytest.raises(device.NoTpuError):
        make()


@pytest.mark.parametrize("ec_engine", ["tpu", "mesh"])
def test_volume_store_refuses_to_start_without_tpu(unpinned, tmp_path,
                                                   ec_engine):
    from seaweedfs_tpu.volume_server.store import Store

    with pytest.raises(device.NoTpuError):
        Store([str(tmp_path)], ec_engine=ec_engine)


def test_auto_engine_picks_the_host_codec_off_tpu():
    """No engine is picked for the caller: off (and on) the TPU the
    host codec is the CPU path, ec/encoder.write_ec_files, which the
    streaming encoder names when asked for "auto"."""
    from seaweedfs_tpu.ec.streaming import StreamingEncoder

    with pytest.raises(ValueError, match="ec/encoder.write_ec_files"):
        StreamingEncoder(engine="auto")


@pytest.fixture
def fresh_cache_setup(monkeypatch):
    """ensure_compile_cache as a new process would run it on a TPU,
    with jax.config writes recorded instead of applied."""
    updates = {}
    monkeypatch.setattr(device, "_cache_set_up", False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    monkeypatch.setattr(jax.monitoring, "register_event_listener",
                        lambda cb: None)
    monkeypatch.setattr(jax.monitoring,
                        "register_event_duration_secs_listener",
                        lambda cb: None)
    return updates


def test_compile_cache_at_a_fixed_path_in_the_checkout(fresh_cache_setup,
                                                       monkeypatch):
    import os

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    device.ensure_compile_cache()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert fresh_cache_setup["jax_compilation_cache_dir"] == \
        os.path.join(repo, ".jax_cache")
    device.ensure_compile_cache()  # once per process
    assert len(fresh_cache_setup) == 2


def test_compile_cache_env_is_left_to_jax(fresh_cache_setup, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    device.ensure_compile_cache()
    assert fresh_cache_setup == {}


def test_device_doc_counts_compile_requests():
    import jax.numpy as jnp

    device.ensure_compile_cache()
    before = device.device_doc()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    after = device.device_doc()
    assert after["compile_requests"] > before["compile_requests"]
    assert (after["platform"], after["count"]) == ("cpu", len(jax.devices()))
