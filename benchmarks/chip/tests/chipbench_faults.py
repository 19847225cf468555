"""Faults planted under the timed path, for the tests that see ``correct``
come out false.  Each wraps the compiled step (or its gradient sync)
the way ``harness.set_up(..., step_fault=...)`` expects."""
import jax
import jax.numpy as jnp


def unchanged_state(step, prog):
    """A step that returns its parameters and optimizer state unchanged."""
    def run(p, o, b):
        _, _, m = step(jax.tree.map(jnp.copy, p), jax.tree.map(jnp.copy, o), b)
        return p, o, m
    return run


def half_batch(step, prog):
    """Half of the batch left out: the second half of the rows carry no
    labels, so the mean is taken over the rest."""
    def run(p, o, b):
        lab = b["labels"]
        keep = jnp.arange(lab.shape[0])[:, None] < lab.shape[0] // 2
        return step(p, o, dict(b, labels=jnp.where(keep, lab, -1)))
    return run


def no_exchange(step, prog):
    """The exchange between chips left out: every worker keeps its own
    gradient (the sync is computed and its result dropped)."""
    gs = prog.prog.gradsync

    class Local(type(gs)):
        def __call__(self, grads, *args, **kw):
            out = super().__call__(grads, *args, **kw)
            return (grads,) + tuple(out[1:])

    gs.__class__ = Local
    return step


def altered_loss(step, prog):
    """An answer altered where it is produced: the reported loss off by 1%."""
    def run(p, o, b):
        p, o, m = step(p, o, b)
        return p, o, dict(m, loss=m["loss"] * 1.01)
    return run


ONE_CHIP = {"unchanged_state": unchanged_state, "half_batch": half_batch,
            "altered_loss": altered_loss}
MULTI_CHIP = dict(ONE_CHIP, no_exchange=no_exchange)
