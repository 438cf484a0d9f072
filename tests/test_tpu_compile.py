"""The fold kernels compile for a TPU v5e chip at PreActResNet18 width.

Interpret mode runs each kernel's body on the CPU but not the TPU
compiler's rules (block tiling, scoped VMEM, vector shapes).  These tests
compile every fold kernel for a v5e chip that is described, not attached,
at the real flat width of the paper's model (``n_flat = 11,175,936``) and
a 5-client chunk, and require the Pallas kernel in the compiled program.
Nothing runs.

The topology is described inside a module fixture, never while a module
is imported: only one process at a time may load the TPU library.

Also here: ``chip_smoke.py`` refuses to run without a TPU.
"""

import functools
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.masked_agg import kernel as K

N_FLAT = 11_175_936      # PreActResNet18 (GroupNorm) flat layout, 2048-padded
Z = 5                    # one cohort chunk of the paper protocol
K_TOP = 798_208          # top-k payload at --topk-frac 1/14 of N_FLAT
QUANT_BLOCK = 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache: keep them out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _fold_case(name, spec):
    f32, i8 = jnp.float32, jnp.int8
    acc, mask, w = spec((N_FLAT,), f32), spec((N_FLAT,), jnp.bool_), \
        spec((Z,), f32)
    if name == "acc_f32":
        return K.masked_agg_acc_pallas, (acc, spec((Z, N_FLAT), f32), mask,
                                         w, w)
    if name == "acc_bf16":
        return K.masked_agg_acc_pallas, (acc, spec((Z, N_FLAT),
                                                   jnp.bfloat16), mask, w, w)
    if name == "oneshot":
        return K.masked_agg_pallas, (spec((Z, N_FLAT), f32), mask, w, w)
    if name == "acc_deq":
        return (functools.partial(K.masked_agg_acc_deq_pallas,
                                  quant_block=QUANT_BLOCK),
                (acc, spec((Z, N_FLAT), i8),
                 spec((Z, N_FLAT // QUANT_BLOCK), f32), mask, w, w))
    assert name == "scatter_int8"
    return (functools.partial(K.masked_scatter_acc_pallas,
                              quant_block=QUANT_BLOCK),
            (acc, spec((Z, K_TOP), i8), spec((Z, K_TOP // QUANT_BLOCK), f32),
             spec((Z, K_TOP), jnp.int32), mask, w, w))


@pytest.mark.parametrize("name", ["acc_f32", "acc_bf16", "oneshot",
                                  "acc_deq", "scatter_int8"])
def test_fold_kernel_compiles_for_v5e(one_chip, name):
    spec = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    fn, args = _fold_case(name, spec)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_chip_smoke_refuses_cpu(capsys, monkeypatch):
    assert jax.default_backend() == "cpu"
    monkeypatch.setattr(sys, "path", list(sys.path))
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    with pytest.raises(SystemExit) as exc:
        smoke.main()
    assert exc.value.code not in (0, None)
    assert '"ok": true' not in capsys.readouterr().out
