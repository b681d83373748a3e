"""The knn_shapley check's device footprint, on the CPU at a tiny size.

The check copies the (n,) state on the device before every step and keeps
the copies of its sampled steps. Those are read back when the window is
over, but `bench/run.py` reads the peak device memory before that: copies
left on the device until then count as the program's memory, and grow with
the steps in the window. Here a simulated device runs `LAG` steps behind
the host, and the window is driven as `run.py` drives it, so that a kept
copy becomes ready only once its step is waited for.
"""

import json
from collections import deque
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
LAG = 8  # steps the simulated device runs behind the host
SEED = 2**33 + 18
TINY = {"method": "knn_shapley", "n": 160, "d": 16, "k": 5, "classes": 3,
        "test_pool": 100, "shards": 1, "class_sep": 0.5,
        "check": {"limits": {"values_gap": 1e-4}}}


class Device:
    """Steps issued by the host and steps the device has finished."""

    def __init__(self):
        self.issued = self.done = 0


class Lagging:
    """A device copy that the simulated device makes right after the step
    issued before it. Reading it before then, or waiting for it, fails:
    the check may do neither during the window."""

    def __init__(self, arr, device: Device):
        self.arr, self.device = arr, device
        self.after = device.issued + 1

    def is_ready(self) -> bool:
        return self.device.done >= self.after

    def copy_to_host_async(self) -> None:
        pass

    def block_until_ready(self):
        raise AssertionError("the check waited for the device")

    def __array__(self, dtype=None, copy=None):
        if not self.is_ready():
            raise AssertionError("the check read a copy not made yet")
        return np.asarray(self.arr, dtype)


class LaggingJnp:
    """`jax.numpy` with `array` returning copies the device makes late."""

    def __init__(self, jnp, device: Device):
        self._jnp, self._device = jnp, device

    def array(self, x, copy=True):
        return Lagging(self._jnp.array(x, copy=copy), self._device)

    def __getattr__(self, name):
        return getattr(self._jnp, name)


def device_copies(obj, seen=None) -> int:
    """The device arrays that `obj`'s attributes reach, each counted once."""
    import jax

    seen = set() if seen is None else seen
    if isinstance(obj, (Lagging, jax.Array)):
        seen.add(id(obj))
    elif isinstance(obj, dict):
        for v in obj.values():
            device_copies(v, seen)
    elif isinstance(obj, (list, tuple)):
        for v in obj:
            device_copies(v, seen)
    elif hasattr(obj, "__dict__") and not isinstance(obj, type):
        for v in vars(obj).values():
            device_copies(v, seen)
    return len(seen)


def parent_check(mod):
    """The check as it was before `poll`: every kept copy stays on the
    device until `after_window`."""
    import jax.numpy as jnp

    class Unpolled(mod.Check):
        def poll(self):
            pass

        def before_step(self, step, state):
            if step == 0:
                return
            prev = jnp.array(state[0], copy=True)
            if step - 1 in self.before:
                self.after[step - 1] = prev
            self.last, self.last_copy = step, prev
            if self._sampled(step):
                self.before[step] = prev

    return Unpolled


@pytest.fixture(scope="module")
def tiny():
    import jax.numpy as jnp

    import catalog
    import data
    import run as harness

    stride = json.loads((BENCH / "configs" / "knn-cifar10.json")
                        .read_text())["check"]["snapshot_stride"]
    cfg = dict(TINY, check=dict(TINY["check"], snapshot_stride=stride))
    traffic = {"batch": 16}
    x, y, xb, yb = data.mixture(SEED, n=cfg["n"], pool=cfg["test_pool"],
                                d=cfg["d"], classes=cfg["classes"],
                                sep=cfg["class_sep"], tb=traffic["batch"])
    mod = catalog.Catalog(BENCH.parent).method("knn_shapley")
    return dict(cfg=cfg, traffic=traffic, data=(x, y, xb, yb), mod=mod,
                jnp=jnp, build=harness.build_session)


@pytest.mark.parametrize("steps", [60, 600])
def test_check_holds_three_copies_whatever_the_window(tiny, monkeypatch,
                                                      steps):
    cfg, mod = tiny["cfg"], tiny["mod"]
    stride = cfg["check"]["snapshot_stride"]
    x, y, xb, yb = tiny["data"]
    device = Device()
    monkeypatch.setattr(mod, "jnp", LaggingJnp(tiny["jnp"], device))
    session = tiny["build"](cfg, tiny["traffic"], x, y)
    check = mod.Check(cfg, SEED, 1)
    parent = parent_check(mod)(cfg, SEED, 1)
    session.update(xb[0], yb[0])  # the warm step
    fed, queue, held = [0], deque(), []
    for step in range(1, steps + 1):
        b = step % len(xb)
        check.before_step(step, session._state)
        parent.before_step(step, session._state)
        session.update(xb[b], yb[b])
        fed.append(b)
        device.issued = step
        queue.append(step)
        if len(queue) > LAG:
            device.done = queue.popleft()
            check.poll()
        held.append(device_copies(check))
    while queue:  # run.py's drain, one poll after each step waited for
        device.done = queue.popleft()
        check.poll()

    # what the peak read sees: the copy of the last step, whatever the
    # number of steps; the parent's check holds two a sampled step
    assert device_copies(check) <= 3
    assert device_copies(parent) >= 2 * (steps // stride)
    # during the window: the kept copies of the queued steps, bounded by
    # the queue's depth, not by the steps in the window
    assert max(held) <= 2 * (-(-(LAG + 1) // stride) + 1) + 1

    # the same sampled steps, contributions and numbers as the parent's
    check.after_window(session)
    parent.after_window(session)
    assert sorted(check.got) == sorted(parent.got)
    assert len(check.got) >= steps // stride
    for key in parent.got:
        np.testing.assert_array_equal(check.got[key], parent.got[key])
        np.testing.assert_array_equal(check.slack[key], parent.slack[key])
    want = check.reference(fed, x, y, xb, yb)
    numbers, parts = check.numbers(check.got, want)
    assert (numbers, parts) == parent.numbers(parent.got, want)
    assert numbers["values_gap"] <= cfg["check"]["limits"]["values_gap"]
