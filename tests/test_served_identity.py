"""A model with no rope scaling and no expert layer serves exactly what
it served before the expert layer and YaRN came in: a tiny Qwen3's packed
prefill logits, per-token paged decode logits and macro-served tokens,
digested bit for bit and compared with the digest recorded on the CPU
from the code before that change."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as C
from repro.memtier import SharedPagedPools, TierConfig, TieringManager
from repro.models import model as mdl
from repro.serve import sched as S

#: sha256 of the served logits and tokens below, from the earlier code
DIGEST = "eb3084c9d953a2c766c98db1bc739ea9d76cf1151549622af9617a6ec7ec355e"


def test_qwen3_serves_bit_identical_logits(monkeypatch):
    cfg = C.reduced("qwen3-14b")
    assert cfg.rope_scaling is None and cfg.moe is None
    params, _ = mdl.init(jax.random.PRNGKey(7), cfg)
    rng = np.random.default_rng(11)
    toks = rng.integers(0, cfg.vocab_size, (2, 32)).astype(np.int32)
    lp, _ = jax.jit(mdl.prefill_batched, static_argnums=1)(
        params, cfg, jnp.asarray(toks), jnp.asarray([32, 19], np.int32))
    h = hashlib.sha256(np.asarray(lp).tobytes())
    captured = []
    real = S._decode_paged

    def capture(*a, **kw):
        out = real(*a, **kw)
        captured.append(np.asarray(out[0]))
        return out

    monkeypatch.setattr(S, "_decode_paged", capture)
    for macro in (False, True):
        pools = SharedPagedPools.create(64, 32)
        mon = S.TrafficMonitor(pools, TieringManager(64, TierConfig(
            page_size=8, hbm_pages=32, period_steps=4)))
        b = S.ContinuousBatcher(params, cfg, max_active=2, max_len=64,
                                page_size=8, monitor=mon, macro=macro)
        for i in range(2):
            b.submit(S.Request(rid=i, prompt=rng.integers(
                0, cfg.vocab_size, 17 + 5 * i).astype(np.int32),
                max_new_tokens=9, temperature=0.0))
        out = []
        while b.active or b.queue:
            out.extend(b.step())
        h.update(np.asarray(out, np.int64).tobytes())
    assert len(captured) == 8
    for c in captured:
        h.update(c.tobytes())
    assert h.hexdigest() == DIGEST
