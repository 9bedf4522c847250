"""R2 bite fixture: host syncs in the wrong tick phases.

Mirrors the engine's tick shape — a method that emits phase slices via
``self.tracer.tick`` — with syncs planted in the dispatch phase and in
a helper reached from it.  Parsed only, never executed.
"""

import numpy as np


class FakeEngine:
    def step(self):
        t0 = self.tracer.now_us() if self.tracer is not None else -1.0
        self._admit()
        t1 = self.tracer.now_us() if self.tracer is not None else -1.0
        nxt = self._dispatch_mixed(self._tables())
        depth = self.queue_depth.item()  # BITE .item() in dispatch phase
        early = np.asarray(nxt)  # BITE asarray(dispatch result) pre-sync
        nxt.block_until_ready()  # BITE block_until_ready
        t2 = self.tracer.now_us() if self.tracer is not None else -1.0
        nxt_host = np.asarray(nxt)  # designated host_sync: NOT a finding
        fin_host = np.asarray(nxt)  # BITE second fetch after the designated one
        t3 = self.tracer.now_us() if self.tracer is not None else -1.0
        self._deliver(nxt_host, early, depth)
        wm = self.watermark_dev.item()  # BITE third sync in deliver
        t4 = self.tracer.now_us() if self.tracer is not None else -1.0
        if self.tracer is not None:
            self.tracer.tick(t0, (
                ("admission", t0, t1), ("mixed_dispatch", t1, t2),
                ("host_sync", t2, t3), ("deliver", t3, t4),
            ))
        return int(fin_host[0]) + wm  # host-side read: NOT a finding

    def _admit(self):
        import jax

        lens = self._lengths()
        return jax.device_get(lens)  # BITE device_get in reached helper

    def _tables(self):
        return np.zeros((2, 2), np.int32)  # host packing: NOT a finding

    def _lengths(self):
        return [1, 2]

    def _dispatch_mixed(self, tables):
        return tables

    def _deliver(self, nxt_host, early, depth):
        # deliver phase body in the tick is exempt; this helper is only
        # reached from the exempt span, so it is not scanned
        return int(nxt_host[0]) + depth


class ReplicaSet:
    """The fleet tick (FLEET_TICK_METHODS): no tracer.tick phase tuple,
    so there is NO exempt span — any sync in the loop stalls every
    replica at once."""

    def step(self):
        has_work = False
        for engine in self.engines:
            has_work |= engine.step()
        self.loads.append(self.depth_dev.item())  # BITE .item() in the fleet tick
        return has_work and self._any_alive()

    def _any_alive(self):
        import jax

        return jax.device_get(self.alive_dev)  # BITE device_get in reached helper

    def snapshot(self):
        # not a tick method and not reached from one: not scanned
        return float(self.depth_dev.item())
