"""Faults planted under the timed path, for the control and the tests.

The benchmark's own runs never use these. `control.py` runs them on the chip
to read the upper end of each limit, and the test file drives a whole
rehearsal with each one planted and sees `correct` come out false. Each is a
context manager that patches the program from outside (the program has no
such switch) and restores it.

  shed       THE CONTROL: the shortcut that would tempt a later PR. One
             update step in 64 is skipped and its batch is neither absorbed
             nor counted as shed, which breaks the configuration's
             "every shed event is counted"
  unchanged  every update step returns its state unchanged
  half       every second event of every batch is left out (weight 0)
  nomerge    the exchange between chips is left out: the sharded harvest
             answers from lane 0 alone (cells on four chips)
  altered    an answer is altered where it is produced: the digest's
             largest heavy-hitter count loses 1%, its distinct count gains 10%
  inflated   answers altered the other way: every heavy-hitter count of the
             digest gains 1% (over-counts, which `hh_under` cannot see)
  dropped    the digest's largest heavy hitter is reported under another key:
             the key the bound guarantees a place is missing
  narrow     the entropy plane loses an index bit: after every step its
             histogram is folded onto its lower half, so the digest reads
             the entropy of a histogram half as wide (a kernel that drops a
             bit of the bucket index would do this; every other plane is sound)
"""

from __future__ import annotations

import contextlib


@contextlib.contextmanager
def planted(name: str):
    import jax.numpy as jnp
    import numpy as np

    from inspektor_gadget_tpu.operators import tpusketch

    ingest, decode = tpusketch._ingest_jit, tpusketch.decode_digest
    harvest = tpusketch.make_bundle_harvest_sharded
    make_sharded = tpusketch.make_bundle_ingest_sharded
    calls = {"n": 0}

    # each takes the sound step first, so it wraps the one-chip step and
    # the sharded step (stacked state, (chips, batch) lanes) alike
    def skip(_step, bundle, *_a):
        return bundle, bundle.events + 0.0

    def shed(step, bundle, *a):
        calls["n"] += 1
        return skip(step, bundle) if calls["n"] % 64 == 0 else step(bundle, *a)

    def half(step, bundle, hh, distinct, dist, w, *rest):
        return step(bundle, hh, distinct, dist,
                    w * (jnp.arange(w.shape[-1]) % 2 == 0), *rest)

    def narrow(step, bundle, *a):
        new, token = step(bundle, *a)
        c = new.entropy.counts
        h = c.shape[-1] // 2
        folded = jnp.concatenate(
            [c[..., :h] + c[..., h:], jnp.zeros_like(c[..., h:])], axis=-1)
        return new.replace(entropy=new.entropy.replace(counts=folded)), token

    def under_step(fault):
        import functools
        tpusketch._ingest_jit = functools.partial(fault, ingest)
        tpusketch.make_bundle_ingest_sharded = lambda mesh, like: (
            functools.partial(fault, make_sharded(mesh, like)))

    def altered(digest):
        ev, dr, distinct, ent, approx, keys, counts = decode(digest)
        counts = np.array(counts)
        if counts.size and counts.max() > 0:
            counts[counts.argmax()] -= max(int(counts.max()) // 100, 1)
        return ev, dr, distinct * 1.1, ent, approx, keys, counts

    def inflated(digest):
        ev, dr, distinct, ent, approx, keys, counts = decode(digest)
        counts = np.array(counts)
        return ev, dr, distinct, ent, approx, keys, counts + counts // 100

    def dropped(digest):
        ev, dr, distinct, ent, approx, keys, counts = decode(digest)
        keys = np.array(keys)
        if keys.size:
            keys[np.argmax(counts)] ^= np.uint32(0x5BD1E995)
        return ev, dr, distinct, ent, approx, keys, counts

    def nomerge(_mesh, _like):
        import jax
        return lambda stacked: jax.tree.map(lambda x: x[0], stacked)

    if name in ("shed", "unchanged", "half", "narrow"):
        under_step({"shed": shed, "unchanged": skip, "half": half,
                    "narrow": narrow}[name])
    elif name == "nomerge":
        tpusketch.make_bundle_harvest_sharded = nomerge
    elif name in ("altered", "inflated", "dropped"):
        tpusketch.decode_digest = {"altered": altered, "inflated": inflated,
                                   "dropped": dropped}[name]
    else:
        raise KeyError(f"no fault {name!r}")
    try:
        yield
    finally:
        tpusketch._ingest_jit, tpusketch.decode_digest = ingest, decode
        tpusketch.make_bundle_harvest_sharded = harvest
        tpusketch.make_bundle_ingest_sharded = make_sharded
