"""The XLA path of ``cross_entropy_loss`` (``modules._token_nll``) takes
the label's logit by compare-and-sum, not by a gather, and writes its
backward out: the same value and gradient as the gathered loss under
autodiff, and a backward pass that holds no scatter into the logits (on a
TPU XLA runs that scatter on a flattened copy of a one-sequence
microbatch's logits: two relayouts of the whole f32 array to add a
microbatch's 4096 numbers)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_galvatron_tpu.models.modules import cross_entropy_loss

S, V = 16, 384      # (a vocabulary padded to 384 columns)


def _gathered_loss(logits, labels, loss_mask=None, z_loss=0.0):
    """The loss as it was: the label's logit by ``take_along_axis``."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    nll = lse - jnp.take_along_axis(logits, labels[..., None],
                                    axis=-1)[..., 0]
    if z_loss:
        nll = nll + z_loss * jnp.square(lse)
    if loss_mask is None:
        return jnp.mean(nll)
    loss_mask = loss_mask.astype(jnp.float32)
    return jnp.sum(nll * loss_mask) / jnp.maximum(jnp.sum(loss_mask), 1.0)


def _data(rows, column, masked):
    k1, k2, k3 = jax.random.split(jax.random.key(rows), 3)
    logits = 3.0 * jax.random.normal(k1, (rows, S, V), jnp.float32)
    labels = jax.random.randint(k2, (rows, S), 0, V, jnp.int32)
    # every row's first labels at the column under test
    labels = labels.at[:, :4].set(column)
    mask = (jax.random.bernoulli(k3, 0.7, (rows, S)).astype(jnp.float32)
            .at[:, 0].set(1.0) if masked else None)
    return logits, labels, mask


@pytest.mark.parametrize("column", [0, V - 1],
                         ids=["column0", "last_padded_column"])
@pytest.mark.parametrize("masked", [False, True], ids=["nomask", "mask"])
@pytest.mark.parametrize("z_loss", [0.0, 1e-4])
@pytest.mark.parametrize("rows", [1, 2])
def test_value_and_gradient_are_the_gathered_loss(rows, z_loss, masked,
                                                  column):
    logits, labels, mask = _data(rows, column, masked)
    got, got_g = jax.value_and_grad(cross_entropy_loss)(
        logits, labels, mask, z_loss)
    want, want_g = jax.value_and_grad(_gathered_loss)(
        logits, labels, mask, z_loss)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got_g, want_g, rtol=1e-6, atol=1e-9)
    # the label's column holds the one negative term of its row
    at = np.asarray(got_g)[0, 0]
    assert at[column] < 0 and (np.delete(at, column) > 0).all()


def _primitives(jaxpr):
    """(primitive name, its first operand's element count) of every
    equation, those of inner jaxprs too."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, (
            int(np.prod(eqn.invars[0].aval.shape)) if eqn.invars else 0)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


@pytest.mark.parametrize("rows", [1, 2])
def test_no_scatter_into_the_logits_and_no_gather_from_them(rows):
    logits, labels, mask = _data(rows, 0, True)
    loss = lambda x: cross_entropy_loss(x, labels, mask, z_loss=1e-4)
    forward = jax.make_jaxpr(loss)(logits)
    backward = jax.make_jaxpr(jax.grad(loss))(logits)
    for name, size in list(_primitives(forward.jaxpr)) + list(
            _primitives(backward.jaxpr)):
        assert not (name.startswith(("scatter", "gather"))
                    and size == logits.size), name
    # (the gathered loss is what the check is for)
    old = jax.make_jaxpr(jax.grad(_gathered_loss))(logits, labels, mask)
    assert ("scatter-add", logits.size) in set(_primitives(old.jaxpr))
