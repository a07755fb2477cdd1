"""Faults planted in the timed path, to see ``correct`` come out false.

Each fault takes ``patch(owner, attr, value)`` (``monkeypatch.setattr``
in a test, :class:`Patch` in ``chipbench/control.py``) and breaks the
program underneath the harness, where the results are produced.  The
cells run on one chip, so there is no exchange between chips to leave
out.
"""

from __future__ import annotations


def _unchanged_state(patch):
    """The replay step returns its state unchanged: nothing is replayed."""

    import jax

    from repro.core import engine_device as ed

    patch(ed, "_jitted_program",
          lambda: jax.jit(lambda g, lanes, st, ev: ed._final_drain(g, st)))


def _half_batch(patch):
    """Half of every lane's event tape is left out of the replay."""

    from repro.core import engine_device as ed

    real = ed.replay_lanes

    def half(events, lanes, state0, **kw):
        events = dict(events)
        n = events["valid"].shape[0]
        events["valid"] = events["valid"].copy()
        events["valid"][n // 2:] = False
        return real(events, lanes, state0, **kw)

    patch(ed, "replay_lanes", half)


def _altered(field, change):
    """One result field of every lane altered where the replay returns it."""

    def plant(patch):
        from repro.core import engine_device as ed

        real = ed.replay_lanes

        def altered(*args, **kw):
            out = real(*args, **kw)
            out[field] = change(out[field])
            return out

        patch(ed, "replay_lanes", altered)
    return plant


FAULTS = {
    "state_unchanged": _unchanged_state,
    "half_batch": _half_batch,
    "bytes_altered": _altered("bytes_to_ssd", lambda b: b + 4096),
    "clock_altered": _altered("io_seconds", lambda t: t * (1 + 1e-6)),
}


class Patch:
    """``patch(owner, attr, value)`` that ``undo()`` reverses."""

    def __init__(self):
        self._undo: list[tuple[object, str, object]] = []

    def __call__(self, owner, attr: str, value) -> None:
        self._undo.append((owner, attr, getattr(owner, attr)))
        setattr(owner, attr, value)

    def undo(self) -> None:
        while self._undo:
            owner, attr, value = self._undo.pop()
            setattr(owner, attr, value)
