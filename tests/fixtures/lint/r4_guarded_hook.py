"""R4 bite fixture: unguarded optional-hook calls and the cached-hook
anti-pattern.  Parsed only, never executed."""


class Engine:
    def step_unguarded_attr(self):
        self.tracer.instant("tick")  # BITE direct call, no is-None guard

    def step_unguarded_local(self):
        tr = self.tracer
        tr.instant("tick")  # BITE local hook call, no is-None guard

    def step_unguarded_faults(self):
        if self.faults.trip("decode") is not None:  # BITE faults unguarded
            raise RuntimeError("boom")

    def step_unguarded_actions(self):
        self.actions.on_tick([], None)  # BITE actions hook unguarded

    def step_unguarded_telemetry(self):
        self.telemetry.mixed_tick_cost(self, [], [])  # BITE telemetry hook unguarded

    def push_unguarded_otel(self, ev):
        self.otel.offer(ev)  # BITE otel sink unguarded

    def plan_unguarded_host_tier(self, keys):
        return self.host_tier.match(keys)  # BITE host_tier hook unguarded

    def finish_unguarded_tenants(self, req):
        self.tenants.on_terminal(req)  # BITE tenants ledger unguarded

    def step_unguarded_phase_mark(self):
        t0 = self._phase_mark("serve.admission")  # BITE tracing-only call, no guard
        cpu = time.thread_time_ns()  # BITE tick-thread CPU clock when off
        with jax.profiler.TraceAnnotation("serve.x"):  # BITE scope when off
            pass
        t1 = (self.tracer.now_us() if self.tracer is not None
              else self._phase_mark(None))  # BITE the untaken arm
        return t0, cpu, t1

    def step_guarded_phase_mark(self):
        t0 = (self._phase_mark("serve.admission")
              if self.tracer is not None else -1.0)  # guarded: NOT a finding
        if self.tracer is not None and t0 >= 0.0:
            cpu = time.thread_time_ns()  # guarded: NOT a finding
        with (jax.profiler.TraceAnnotation("serve.x")
              if self.tracer is not None else None):  # guarded
            pass
        return t0, cpu

    def step_guarded(self):
        if self.tracer is not None:
            self.tracer.instant("tick")  # guarded: NOT a finding
        faults = self.faults
        if faults is not None:
            faults.trip("decode")  # guarded local: NOT a finding
