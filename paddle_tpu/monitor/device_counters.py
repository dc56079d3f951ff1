"""paddle_tpu.monitor.device_counters — counters that live on the device.

A host counter (``monitor.counter``) is bumped by Python: once per call
of a compiled step at best, and never with a value the step computed. A
device counter is a small int32 array that the step itself adds to —
state of the compiled step like a batch norm's running mean, carried
from call to call with no host sync — registered here under the names of
its entries and read on demand::

    stats = Tensor(jnp.zeros(4, jnp.int32))          # a Layer buffer
    monitor.device_counters.register(("moe.slots_routed_here", ...), stats,
                                     owner=layer)
    ... thousands of compiled steps add to stats ...
    monitor.device_counters.read()       # {"moe.slots_routed_here": n, ...}

``read()`` is the only transfer, and gives each name's total since the
process began (or ``reset()``), over every source of that name, as Python
integers. The registry keeps the counter arrays themselves (a few bytes
each) and only a weak reference to the ``owner`` that made them: a model
that has been freed can still be read, holding a counter keeps no model
alive, and the first ``read()`` after an owner is gone folds its count
into the totals and lets its array go. The arrays count modulo 2**32 and
``read()`` adds up the differences between reads, so a total is right as
long as no entry gains 2**32 between two reads. Registration is
unconditional — one list append when a layer is built — and does not
depend on ``monitor.enable()``.
"""
from __future__ import annotations

import threading
import weakref

import numpy as np

__all__ = ["register", "read", "reset"]

_lock = threading.Lock()
_sources = []       # [_Source]
_retired = {}       # name -> what sources whose owner is gone had counted


class _Source:
    def __init__(self, names, holder, owner):
        self.names, self.holder = names, holder
        self.owner = None if owner is None else weakref.ref(owner)
        self.last = np.zeros(len(names), np.uint32)
        self.total = [0] * len(names)

    def gone(self):
        return self.owner is not None and self.owner() is None


def register(names, holder, owner=None):
    """``holder`` (a Tensor of int32 zeros whose ``.data`` the step
    replaces) counts ``names[i]`` in entry ``i``; ``owner`` is the object
    whose life the counter shares (the layer), if any."""
    names = tuple(str(n) for n in names)
    if tuple(holder.shape) != (len(names),):
        raise ValueError(f"a counter array of shape {tuple(holder.shape)} "
                         f"cannot count {len(names)} names")
    with _lock:
        _sources.append(_Source(names, holder, owner))
    return holder


def read(prefix=""):
    """{name: total over its sources} as Python ints, in one transfer."""
    import jax
    with _lock:
        values = jax.device_get([s.holder.data for s in _sources])
        for s, raw in zip(_sources, values):
            raw = np.asarray(raw, np.int32).view(np.uint32)
            gained = raw - s.last               # modulo 2**32, as counted
            s.total = [t + int(g) for t, g in zip(s.total, gained)]
            s.last = raw
        for s in [s for s in _sources if s.gone()]:
            for name, t in zip(s.names, s.total):
                _retired[name] = _retired.get(name, 0) + t
            _sources.remove(s)
        out = dict(_retired)
        for s in _sources:
            for name, t in zip(s.names, s.total):
                out[name] = out.get(name, 0) + t
    return {k: v for k, v in out.items() if k.startswith(prefix)}


def reset():
    """Forget every source and total (tests; a new run in one process)."""
    with _lock:
        _sources.clear()
        _retired.clear()
