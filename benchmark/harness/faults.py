"""Faults planted under the timed path. A run driven with one of them
has to come out as not correct: the tests plant each at toy size, and
``tools/sweep.py --fault <name>`` plants it at the cell's own size on
the chip. No cell sets one."""

from __future__ import annotations


def state_unchanged(window, state, po, pt, pv):
    """A step that returns its state unchanged."""
    return state


def half_batch(window, state, po, pt, pv):
    """Half of every publish batch left out."""
    return window(state, po.at[:, po.shape[1] // 2:].set(-1), pt, pv)


def answer_altered(window, state, po, pt, pv):
    """One peer forgets the first message it holds, bit and round kept."""
    import jax.numpy as jnp

    st = window(state, po, pt, pv)
    dlv = st.core.dlv
    peer = jnp.argmax(dlv.have[:, 0] != 0)
    low = dlv.have[peer, 0] & (~dlv.have[peer, 0] + 1)
    dlv = dlv.replace(have=dlv.have.at[peer, 0].set(dlv.have[peer, 0] ^ low))
    return st.replace(core=st.core.replace(dlv=dlv))


def fmd_dropped(window, state, po, pt, pv):
    """The first-delivery counters never credited (scored cells)."""
    import jax.numpy as jnp

    st = window(state, po, pt, pv)
    return st.replace(score=st.score.replace(fmd=jnp.zeros_like(st.score.fmd)))


FAULTS = {f.__name__: f for f in
          (state_unchanged, half_batch, answer_altered, fmd_dropped)}


def plant(built, name: str) -> None:
    """Wrap the window the driver will time with fault ``name``."""
    wrap = FAULTS[name]
    make = built.make_window

    def broken(unroll):
        window = make(unroll)

        def run(state, po, pt, pv):
            return wrap(window, state, po, pt, pv)

        run._cache_size = window._cache_size
        return run

    built.make_window = broken
