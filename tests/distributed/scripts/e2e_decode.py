"""E2E: prefill -> serve_step decode must equal full-sequence forward."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.core.sharding import HelixConfig, default_helix_config
from repro.models.transformer import init_params, forward
from repro.models.model_zoo import make_prefill_step, build_serve_step
from repro.utils import make_mesh, set_mesh

mesh = make_mesh((4, 2), ("data", "model"))

for arch in ["granite-3-2b", "gemma3-12b", "granite-moe-1b-a400m",
             "mamba2-780m", "hymba-1.5b", "whisper-base", "phi-3-vision-4.2b"]:
    cfg = get_config(arch).reduced()
    hx = default_helix_config(cfg, mesh)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    B, T = 4, 24
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T + 2), 0, cfg.vocab)
    batch = {"tokens": tokens[:, :T]}
    if cfg.vision_patches:
        batch["patch_embeds"] = jax.random.normal(
            jax.random.PRNGKey(2), (B, cfg.vision_patches, cfg.d_model)) * 0.02
    if cfg.is_encdec:
        batch["enc_frames"] = jax.random.normal(
            jax.random.PRNGKey(3), (B, T, cfg.d_model)) * 0.02

    prefill = make_prefill_step(cfg, mesh, hx, s_cap=256)
    serve = build_serve_step(cfg, mesh, hx, hopb_chunks=2, return_logits=True)

    with set_mesh(mesh):
        last_logits, state = jax.jit(prefill)(params, batch)
        (nt1, lg1), state = jax.jit(serve)(params, state, tokens[:, T])
        (nt2, lg2), state = jax.jit(serve)(params, state, tokens[:, T + 1])

    # reference: full forward over T+2 tokens
    fb = dict(batch); fb["tokens"] = tokens
    kw = {}
    if cfg.vision_patches: kw["patch_embeds"] = batch["patch_embeds"]
    if cfg.is_encdec: kw["enc_frames"] = batch["enc_frames"]
    ref_logits, _ = forward(cfg, params, tokens, tp_width=1, **kw)

    for name, got, want in [("prefill", last_logits, ref_logits[:, T - 1]),
                            ("step1", lg1, ref_logits[:, T]),
                            ("step2", lg2, ref_logits[:, T + 1])]:
        g = np.asarray(got, np.float32)[:, :cfg.vocab]
        w = np.asarray(want, np.float32)[:, :cfg.vocab]
        err = np.abs(g - w).max()
        assert err < 2e-3, (arch, name, err)
    print(f"{arch:24s} prefill+2 decode steps == forward  OK")

# ---- kernel-backed prefill on the mesh: GSPMD cannot partition a Pallas
# kernel, so the forward runs flash_prefill on each (batch x head) shard
# (MeshPolicy.per_shard); a batch the data axis does not divide (3 rows
# over 4 shards) is replicated over it instead ----
import dataclasses
cfg = get_config("granite-3-2b").reduced()
params = init_params(cfg, jax.random.PRNGKey(0))
hx = dataclasses.replace(default_helix_config(cfg, mesh),
                         prefill_backend="pallas-interpret")
prefill = jax.jit(make_prefill_step(cfg, mesh, hx, s_cap=256))
for B in (4, 3):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, 24), 0, cfg.vocab)
    with set_mesh(mesh):
        last_logits, _ = prefill(params, {"tokens": tokens})
    ref_logits, _ = forward(cfg, params, tokens, tp_width=1)
    err = np.abs(np.asarray(last_logits, np.float32)[:, :cfg.vocab]
                 - np.asarray(ref_logits, np.float32)[:, -1, :cfg.vocab]).max()
    assert err < 2e-3, (B, err)
    print(f"pallas-interpret prefill on the (4, 2) mesh, B={B} == forward  OK")
print("ALL OK")
