"""MusicGen-Large [audio] — Copet et al., "Simple and Controllable Music
Generation", arXiv:2306.05284; Audiocraft's ``musicgen`` LM config and the
``facebook/musicgen-large`` config.

A 48-layer decoder, d_model 2048, 32 heads of 64 (MHA), d_ff 8192, over 4
parallel EnCodec codebooks of 2048 entries (50 frames a second) in the
*delay pattern*: codebook k runs k steps late, the gaps hold a special
token (id 2048, a 2049th embedding row).  The 4 embeddings are summed
unscaled, and sinusoidal absolute positions are added (cos then sin,
period 10000, ``half_dim - 1`` in the exponent).  Each layer is
pre-LayerNorm (with bias, eps 1e-5): causal self-attention, then
cross-attention to the text conditioning, then an exact-GELU FFN; no bias
on the attention projections or the FFN.  A final LayerNorm feeds 4
untied linear heads (no bias).  The loss is the mean over codebooks of
each codebook's CE over its valid labels; the delay pattern's special
tokens are left out.  The conditioning is the T5-base encoder output
(768 wide), projected to 2048 by a linear layer with bias; padding tokens
of the conditioning are zeroed and masked from the cross-attention.
About 3.26 B parameters.

FedHeN's split: simple = the first ``exit_layer`` = 24 layers, the exit
LayerNorm and the shared codebook heads; complex = all 48 layers with the
side objective.

Departures from the paper:

* the T5 encoder and the EnCodec codec are stubs: the batch carries the
  conditioning embeddings (``cond``, with ``cond_mask``) and the EnCodec
  tokens directly.  MusicGen trains on precomputed tokens and keeps T5
  frozen, so neither runs in its training step either;
* no conditioning dropout for classifier-free guidance;
* the client optimizer is the program's SGD with global-norm clipping;
  the paper trains with AdamW.
"""

from repro.configs.base import LayerSpec, ModelConfig, StubFrontend

CONFIG = ModelConfig(
    name="musicgen-large",
    arch_type="audio",
    source="arXiv:2306.05284",
    n_layers=48,
    d_model=2048,
    n_heads=32,
    n_kv_heads=32,
    head_dim=64,
    d_ff=8192,
    vocab_size=2048,          # EnCodec codebook size (card)
    n_codebooks=4,            # in the delay pattern
    positions="sin",
    rope_theta=10000.0,       # the sinusoid's max period
    norm="layer",
    norm_eps=1e-5,
    embed_scale=False,
    tie_embeddings=False,     # one linear head per codebook
    mlp_glu=False,            # plain transformer FFN (Audiocraft)
    gelu_exact=True,
    pattern=(LayerSpec("attn"),),
    frontend=StubFrontend(kind="text_conditioning", n_tokens=64, d_in=768),
    cross_attention=True,
    exit_layer=24,
    param_dtype="bfloat16",
)


def reduced() -> ModelConfig:
    return CONFIG.with_overrides(
        n_layers=2, d_model=128, n_heads=4, n_kv_heads=4, head_dim=32,
        d_ff=256, vocab_size=64, exit_layer=1, n_codebooks=2,
        frontend=StubFrontend(kind="text_conditioning", n_tokens=4, d_in=32),
        param_dtype="float32", compute_dtype="float32")
