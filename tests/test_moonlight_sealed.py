"""(a), sealed: Moonlight's reduced model served with ColoE-sealed weights
(experts through ``sealed_gmm``, the rest through ``sealed_matmul``) over a
sealed, MAC-verified latent cache gives the plain reference's logits
within the tolerance the float8 control fails. A file of its own: the
interpret-mode kernels take minutes to compile on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np

from moonlight_helpers import (assert_close_to_reference, reference,
                               served_logits)
from repro.config import SealConfig
from repro.configs import get_reduced
from repro.models import transformer as T


def test_sealed_served_logits_match_the_reference():
    cfg = get_reduced("moonlight_16b_a3b")
    key = jax.random.key(11)
    params = T.init_params(cfg, key)
    toks = jnp.asarray(np.random.RandomState(11).randint(
        0, cfg.vocab_size, (2, 30)), jnp.int32)
    seal = SealConfig(mode="coloe", smart_ratio=0.5, verify=True)
    served, ok = served_logits(cfg, params, toks, plen=20, chunk=8,
                               seal=seal)
    assert ok
    pos = jnp.broadcast_to(jnp.arange(19, 29), (2, 10))
    assert_close_to_reference(served, reference(cfg, key, toks, pos),
                              reference(cfg, key, toks, pos, quant="fp8"))
