"""The program against the plain reference at phi-3-vision's MHA head.

A 2-layer preset of phi-3-vision's backbone with its head size (4 heads of
96, one KV head per query head, ``d_model`` 384), on seeded random weights
from ``bench/weights.py``: the program prefills the 14 state tokens, moves
the cache into a page pool and decodes a whole 56-token chunk through the
paged decode op (``CloudPolicy.step_logits``).  Its logits at every
position, the prefill's last and each decode step's, are compared with
``bench/reference.forward`` over the prompt and the tokens the program fed.

Tolerance: the largest absolute logit difference, against 0.09.  The
program keeps weights, activations and the KV pool in bf16 (8 significant
bits); over 8 seeds its logits (rms ~1, largest ~4.9) read 0.025-0.032
from the float32 reference.  The fp8 control (3 significant bits) read
0.275-0.321, so a program one precision lower fails by about 3x.
"""

import jax
import numpy as np
import pytest

import reference
from repro.configs.base import get_config
from repro.data.pipeline import EpisodeTokenizer
from repro.launch.serve import CloudPolicy
from repro.models.model import Model
from spec import SIZE_KEYS
from weights import make_weights

LOGIT_TOL = 0.09
CHUNK = 56  # 8 actions x 7 joints


@pytest.fixture(scope="module")
def mha96():
    cfg = get_config("phi-3-vision-4.2b").replace(
        name="phi3v-mha96-test", num_layers=2, d_model=384, num_heads=4,
        num_kv_heads=4, head_dim=96, d_ff=1024, vocab_size=512,
        num_modality_tokens=16,
    )
    model = Model(cfg)
    sizes = {k: getattr(cfg, k) for k in SIZE_KEYS}
    return cfg, model, sizes, jax.eval_shape(model.init, jax.random.PRNGKey(0))


@pytest.mark.parametrize("seed", [3, 2**31 + 17])
def test_paged_decode_matches_reference_at_mha_head_96(mha96, seed):
    cfg, model, sizes, like = mha96
    params = make_weights(like, seed)
    tok = EpisodeTokenizer(cfg.vocab_size)
    rng = np.random.default_rng(seed)
    qd, tau = rng.normal(0, 0.5, (2, 3, 7)).astype(np.float32)
    got, fed = CloudPolicy(model, params, tok, paged=True).step_logits(qd, tau, CHUNK)
    prompt = np.concatenate([tok.encode_state(qd), tok.encode_state(tau)], 1)
    seq = np.concatenate([prompt, fed], 1)
    p = prompt.shape[1]
    want = np.asarray(reference.forward(sizes, params, seq))[:, p - 1:]
    got = got[..., :cfg.vocab_size]
    assert got.shape == want.shape == (3, CHUNK + 1, cfg.vocab_size)
    assert np.abs(got - want).max() <= LOGIT_TOL
    # the control, one precision below bf16, falls outside the tolerance
    ctl = np.asarray(reference.forward(sizes, params, seq, fp8=True))[:, p - 1:]
    assert np.abs(ctl - want).max() > LOGIT_TOL
