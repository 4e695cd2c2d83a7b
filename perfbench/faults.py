"""Faults planted under the timed path, to show that the check catches them.

Each wraps the program's ``make_train_step`` (``repro.runtime.train_loop``);
``plant(name)`` patches it in for the rest of the process, so a Trainer built
after it runs the broken step through the same call and feed.
"""
from __future__ import annotations

IGNORE = -100  # the program's label that the loss leaves out


def _state_unchanged(step):
    """The step computes as before and returns the state it was given."""
    def broken(params, opt_state, batch, *rest):
        out = step(params, opt_state, batch, *rest)
        return (params, opt_state) + tuple(out[2:])
    return broken


def _half_batch(step):
    """Half of the batch left out and the mean taken over the rest: the
    second half of the rows, or of the tokens where the batch is one row."""
    def broken(params, opt_state, batch, *rest):
        lab = batch["labels"]
        if lab.shape[0] >= 2:
            lab = lab.at[lab.shape[0] // 2:].set(IGNORE)
        else:
            lab = lab.at[:, lab.shape[1] // 2:].set(IGNORE)
        return step(params, opt_state, dict(batch, labels=lab), *rest)
    return broken


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch}


def plant(name: str):
    """Patch the fault into the program; returns a function that undoes it."""
    from repro.runtime import train_loop

    orig = train_loop.make_train_step
    train_loop.make_train_step = lambda *a, **kw: FAULTS[name](orig(*a, **kw))

    def undo():
        train_loop.make_train_step = orig
    return undo
