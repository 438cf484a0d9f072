"""The fold kernels compile for a TPU v5e chip at PreActResNet18 width.

Interpret mode runs each kernel's body on the CPU but not the TPU
compiler's rules (block tiling, scoped VMEM, vector shapes).  These tests
compile every fold kernel for a v5e chip that is described, not attached,
at the real flat width of the paper's model (``n_flat = 11,175,936``) and
a 5-client chunk, and require the Pallas kernel in the compiled program,
named after the kernel.  Nothing runs.

A tiny round of the paper's model compiled for the same chip keeps the
round's stage tags (``obs/scopes.py``) on the ops the TPU profiler
reports, and its convolutions plain: one client's batch, two spatial
window dimensions.  A tiny round of MusicGen's block keeps the tags of
the client model's parts on forward and backward ops alike, and at a
sequence length the flash attention kernel takes, runs it, tagged.

The topology is described inside a module fixture, never while a module
is imported: only one process at a time may load the TPU library.

Also here: ``chip_smoke.py`` refuses to run without a TPU.
"""

import functools
import importlib.util
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
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


KERNEL_NAMES = {"acc_f32": "masked_agg_acc", "acc_bf16": "masked_agg_acc",
                "acc_deq": "masked_agg_acc_deq",
                "scatter_int8": "masked_scatter_acc"}


def _custom_calls(hlo: str) -> list:
    """Instruction names of the Mosaic kernels in compiled HLO text."""
    return re.findall(r"%([\w.-]+) = [^\n]*custom_call_target="
                      r'"tpu_custom_call"', hlo)


@pytest.mark.parametrize("name", ["acc_f32", "acc_bf16", "acc_deq",
                                  "scatter_int8"])
def test_fold_kernel_compiles_for_v5e(one_chip, name):
    spec = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    fn, args = _fold_case(name, spec)
    compiled = jax.jit(fn).lower(*args).compile()
    calls = _custom_calls(compiled.as_text())
    # the kernel's own name, not the enclosing function's (``_lambda_``)
    assert [c.rsplit(".", 1)[0] for c in calls] == [KERNEL_NAMES[name]]


def _computations(hlo: str) -> dict:
    """Compiled HLO text -> {computation name: its instruction lines}."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.-]+) .*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name is not None and " = " in line:
            comps[name].append(line.strip())
    return comps


@pytest.fixture(scope="module")
def tiny_round_hlo(one_chip):
    """A tiny round of the paper's model (PreActResNet18-GN at its
    widths, 8x8 images, four clients of one step, chunks of two),
    compiled on shapes placed on the described chip with the Pallas
    fold: its HLO text."""
    from repro.configs.base import FedConfig
    from repro.core.adapters import ResNetAdapter
    from repro.core.federated import FederatedTrainer
    from repro.kernels.masked_agg import ops as agg_ops
    rng = np.random.default_rng(0)
    data = [{"images": jnp.asarray(rng.normal(size=(2, 8, 8, 3)),
                                   jnp.float32),
             "labels": jnp.asarray(rng.integers(0, 10, 2), jnp.int32)}
            for _ in range(4)]
    fed = FedConfig(n_devices=4, n_simple=2, participation=1.0,
                    local_epochs=1, lr=0.1, batch_size=2,
                    algorithm="fedhen", seed=0, cohort_chunk=0)
    tr = FederatedTrainer(ResNetAdapter(), fed, data)
    plan = tr._sample_plan()
    args = tr._round_args(plan, tr._gather(plan.simple_ids),
                          tr._gather(plan.complex_ids),
                          jax.random.PRNGKey(0))
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        args)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(agg_ops, "use_pallas", lambda: True)
        return jax.jit(tr._make_round_fn()).lower(*shapes).compile().as_text()


def test_round_stage_tags_survive_the_v5e_compile(tiny_round_hlo):
    """In the tiny round compiled for the chip every fusion that holds a
    convolution is tagged ``local_sgd``, the fold kernel is tagged
    ``fold`` and named ``masked_agg_acc``, and finalize tags ops of its
    own."""
    hlo = tiny_round_hlo
    comps = _computations(hlo)
    tag = 'fedhen_scope="{}"'.format
    conv_comps = {n for n, lines in comps.items()
                  if any(" convolution(" in ln for ln in lines)}
    conv_fusions = [ln for lines in comps.values() for ln in lines
                    if " fusion(" in ln and re.search(
                        r"calls=%([\w.-]+)", ln).group(1) in conv_comps]
    assert conv_fusions
    assert all(tag("local_sgd") in ln for ln in conv_fusions), \
        [ln[:120] for ln in conv_fusions if tag("local_sgd") not in ln]
    kernels = [ln for lines in comps.values() for ln in lines
               if 'custom_call_target="tpu_custom_call"' in ln]
    assert kernels and all(tag("fold") in ln for ln in kernels)
    assert all(ln.startswith(("%masked_agg_acc.", "ROOT %masked_agg_acc."))
               for ln in kernels)
    assert tag("finalize") in hlo
    # an f32 wire is the identity: the wire stage holds no op
    assert tag("wire") not in hlo
    # no op carries two stages
    assert not re.search(r'fedhen_scope="\w+"[^\n]*fedhen_scope=', hlo)


def test_round_convolutions_are_plain_on_v5e(tiny_round_hlo):
    """Each chunk's clients train one after another, so every convolution
    of the tiny round compiles with a window over the image's two spatial
    dimensions.  Vmapped over per-client weights, the TPU lowers the
    grouped convolution with the client axis as a third, dilated window
    dimension (``size=3x3x2 ... lhs_dilate=1x1x2``) and copies the
    activations between that layout and the elementwise ops' one."""
    windows = re.findall(r" convolution\([^\n]*?window=\{size=([\dx]+)",
                         tiny_round_hlo)
    assert windows
    assert all(len(w.split("x")) == 2 for w in windows), sorted(set(windows))


def _musicgen_round_hlo(sharding, seq_len: int) -> str:
    """A tiny round of MusicGen's block (the registry's reduced config:
    cross-attention, four codebooks in the delay pattern), two clients of
    one step at ``seq_len`` steps, compiled for the chip with the Pallas
    fold and, where it takes the call, the flash attention kernel: its
    HLO."""
    from repro import configs
    from repro.configs.base import FedConfig
    from repro.core.adapters import LMAdapter
    from repro.core.federated import FederatedTrainer
    from repro.data.synthetic import synthetic_conditioning, synthetic_lm
    from repro.kernels.flash_attention import ops as flash_ops
    from repro.kernels.masked_agg import ops as agg_ops
    cfg = configs.get_reduced("musicgen-large").with_overrides(n_codebooks=4)
    fe = cfg.frontend
    data = synthetic_lm(4, seq_len, cfg.vocab_size, n_codebooks=4)
    data.update(synthetic_conditioning(4, fe.n_tokens, fe.d_in))
    shards = [{k: jnp.asarray(v[2 * i:2 * i + 2]) for k, v in data.items()
               if k != "labels"} for i in range(2)]
    fed = FedConfig(n_devices=2, n_simple=1, participation=1.0,
                    local_epochs=1, lr=0.1, batch_size=2,
                    algorithm="fedhen", seed=0, cohort_chunk=0)
    tr = FederatedTrainer(LMAdapter(cfg), fed, shards)
    plan = tr._sample_plan()
    args = tr._round_args(plan, tr._gather(plan.simple_ids),
                          tr._gather(plan.complex_ids),
                          jax.random.PRNGKey(0))
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        args)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(agg_ops, "use_pallas", lambda: True)
        mp.setattr(flash_ops, "use_pallas", lambda: True)
        return jax.jit(tr._make_round_fn()).lower(*shapes).compile().as_text()


@pytest.fixture(scope="module")
def tiny_musicgen_round_hlo(one_chip):
    """16 steps: no flash block divides it, so self-attention is XLA's."""
    return _musicgen_round_hlo(one_chip, 16)


@pytest.fixture(scope="module")
def tiny_musicgen_flash_round_hlo(one_chip):
    """256 steps: self-attention runs the flash kernel."""
    return _musicgen_round_hlo(one_chip, 256)


@pytest.mark.parametrize("part", ["self_attn", "cross_attn", "ffn",
                                  "heads"])
def test_round_part_tags_survive_the_v5e_compile(tiny_musicgen_round_hlo,
                                                 part):
    """In the tiny MusicGen round compiled for the chip, each part of the
    client model tags ops of the forward pass and of the backward pass
    (whose source op names JAX writes as ``transpose(jvp(...))``), inside
    the ``local_sgd`` stage; no op carries two parts."""
    hlo = tiny_musicgen_round_hlo
    tagged = [ln for ln in hlo.splitlines()
              if f'fedhen_part="{part}"' in ln and " = " in ln]
    assert tagged
    assert all('fedhen_scope="local_sgd"' in ln for ln in tagged)
    backward = [ln for ln in tagged if "transpose(jvp(" in ln]
    forward = [ln for ln in tagged if "jvp(" in ln and ln not in backward]
    assert backward and forward, (len(backward), len(forward))
    assert not re.search(r'fedhen_part="\w+"[^\n]*fedhen_part=', hlo)


@pytest.mark.parametrize("kernel", ["flash_attn_fwd", "flash_attn_dkv",
                                    "flash_attn_dq"])
def test_flash_attention_in_the_v5e_round(tiny_musicgen_flash_round_hlo,
                                          kernel):
    """At a sequence length the kernel takes, the round compiled for the
    chip runs self-attention through the three flash kernels, and each
    of them (forward, and the backward ones the custom VJP adds) carries
    the ``self_attn`` part inside the ``local_sgd`` stage, so the part's
    device time counts them.  No op of the part has an S x S result (the
    XLA path's score chunks, masks and probabilities)."""
    hlo = tiny_musicgen_flash_round_hlo
    calls = [ln for ln in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln
             and re.match(rf"\s*(ROOT )?%[\w.]*{kernel}", ln)]
    assert calls
    assert all('fedhen_part="self_attn"' in ln
               and 'fedhen_scope="local_sgd"' in ln for ln in calls)
    results = [re.match(r"\s*(?:ROOT )?%\S+ = (\([^)]*\)|\S+)", ln)
               for ln in hlo.splitlines()
               if 'fedhen_part="self_attn"' in ln and " = " in ln]
    assert not [m.group(1) for m in results
                if m and "256,256]" in m.group(1)]


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
