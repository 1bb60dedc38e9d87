"""Per-architecture smoke tests (deliverable f): every assigned arch at a
reduced config runs one forward + one train step + one decode step on CPU,
asserting output shapes and finiteness."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import decode as D
from repro.models import steps as S
from repro.models import transformer as T
from repro.optim import adamw

ARCHS = list(configs.REGISTRY)


def _batch_for(cfg, batch, seq):
    toks = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                              cfg.vocab)
    labels = jnp.roll(toks, -1, axis=1).at[:, -1].set(-1)
    out = {"tokens": toks, "labels": labels}
    if cfg.family == "vlm":
        p = 8
        out["extra_embeds"] = jnp.zeros((batch, p, cfg.d_model), cfg.cdt)
        out["labels"] = jnp.concatenate(
            [jnp.full((batch, p), -1, jnp.int32), labels], axis=1)
    elif cfg.family == "encdec":
        out["extra_embeds"] = jnp.zeros(
            (batch, cfg.encoder_seq, cfg.d_model), cfg.cdt)
    return out


@pytest.mark.parametrize("arch", ARCHS)
class TestArchSmoke:
    def test_forward_and_train(self, arch):
        cfg = configs.get_config(arch).reduced()
        params = T.init_model(jax.random.PRNGKey(0), cfg)
        batch = _batch_for(cfg, batch=2, seq=32)
        logits, aux = T.forward(params, batch["tokens"], cfg,
                                extra_embeds=batch.get("extra_embeds"))
        s_total = batch["labels"].shape[1] if cfg.family != "encdec" else 32
        assert logits.shape == (2, s_total, cfg.vocab)
        assert np.isfinite(np.asarray(logits, np.float32)).all()

        ocfg = adamw.AdamWConfig(lr=1e-3)
        step = S.make_train_step(cfg, ocfg)
        p2, o2, m = step(params, adamw.init(params, ocfg), batch)
        assert np.isfinite(float(m["loss"]))
        assert np.isfinite(float(m["grad_norm"]))
        # params actually changed
        deltas = [float(np.abs(np.asarray(a, np.float32)
                               - np.asarray(b, np.float32)).max())
                  for a, b in zip(jax.tree.leaves(params),
                                  jax.tree.leaves(p2))]
        assert max(deltas) > 0

    def test_decode_step(self, arch):
        cfg = configs.get_config(arch).reduced()
        params = T.init_model(jax.random.PRNGKey(0), cfg)
        cache = D.init_cache(cfg, batch=2, kv_len=64)
        serve = S.make_serve_step(cfg)
        tok = jnp.zeros((2, 1), jnp.int32)
        logits, new_cache = serve(params, tok, cache, jnp.int32(0))
        assert logits.shape == (2, 1, cfg.vocab)
        assert np.isfinite(np.asarray(logits, np.float32)).all()
        assert set(new_cache) == set(cache)
        for k in cache:
            assert new_cache[k].shape == cache[k].shape, k


class TestDecodeConsistency:
    """Greedy decode over a prompt must match the parallel forward."""

    @pytest.mark.parametrize("arch", ["qwen3-0.6b", "h2o-danube-1.8b",
                                      "rwkv6-3b", "zamba2-7b",
                                      "deepseek-v3-671b",
                                      "kimi-linear-48b-a3b"])
    def test_decode_matches_forward(self, arch):
        cfg = configs.get_config(arch).reduced(n_layers=2)
        params = T.init_model(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(2), (1, 8), 0,
                                  cfg.vocab)
        full, _ = T.forward(params, toks, cfg)
        cache = D.init_cache(cfg, 1, 16)
        serve = S.make_serve_step(cfg)
        lg = None
        for t in range(8):
            lg, cache = serve(params, toks[:, t:t + 1], cache, jnp.int32(t))
        a = np.asarray(lg[0, 0], np.float32)
        b = np.asarray(full[0, -1], np.float32)
        # compare top-choice agreement + numeric closeness
        assert np.abs(a - b).max() < 5e-2, np.abs(a - b).max()
        assert a.argmax() == b.argmax()


class TestConfigRegistry:
    def test_all_archs_present(self):
        assert len(configs.REGISTRY) == 11

    def test_cell_count(self):
        # 11 archs x 4 shapes - 8 long_500k skips = 36
        assert len(configs.cells()) == 36
        assert len(configs.cells(include_skipped=True)) == 44

    def test_exact_assigned_dims(self):
        c = configs.get_config("deepseek-v3-671b")
        assert (c.n_layers, c.d_model, c.n_heads) == (61, 7168, 128)
        assert (c.n_experts, c.top_k, c.d_expert) == (256, 8, 2048)
        c = configs.get_config("qwen2-vl-72b")
        assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads) == \
            (80, 8192, 64, 8)
        c = configs.get_config("rwkv6-3b")
        assert (c.n_layers, c.d_model, c.d_ff, c.vocab) == \
            (32, 2560, 8960, 65536)

    def test_param_counts_close_to_nameplate(self):
        from repro.models.config import count_params
        expect = {"qwen3-0.6b": 0.6e9, "deepseek-v3-671b": 671e9,
                  "qwen2-vl-72b": 72e9, "qwen2.5-3b": 3.1e9,
                  "kimi-linear-48b-a3b": 48e9}
        for name, n in expect.items():
            got = count_params(configs.get_config(name))
            assert abs(got - n) / n < 0.15, (name, got)
