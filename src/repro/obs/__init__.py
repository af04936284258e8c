"""Observability of the query engine, on two clocks.

**Virtual clock: observers of the event stream.** The coordinator exposes
a read-only observer hook: every logged event tuple plus lifecycle kinds
(QUERY_START .. QUERY_DONE) stream to attached observers at the event
pop, and observers never feed anything back — so results are
bit-identical with observability on or off (the no-perturbation
contract, gated by ``benchmarks/obs.py``). Four consumers of that stream
live here:

  * :mod:`repro.obs.trace` — causal span trees (query -> stage -> task
    -> request attempt) with Chrome ``trace_event`` export for
    chrome://tracing / Perfetto;
  * :mod:`repro.obs.metrics` — streaming counters/gauges and mergeable
    log-scale histograms (percentiles without stored samples), memory-
    bounded at fleet scale where the legacy ``event_log`` list is not;
  * :mod:`repro.obs.drift` — rolling-window refits of the GET/PUT
    latency params against a ``planner.calibrate.Calibration``
    reference, flagging regime shifts for the adaptive control plane
    (ROADMAP item 2a);
  * :mod:`repro.obs.report` — per-tenant / per-query-class rollups of
    workload and fleet runs, as text or JSON.

**Wall clock: spans on the profiler's clock.** :mod:`repro.obs.spans`
holds the names of the host spans (``repro.query``, ``repro.task``,
``repro.format.decode``, ``repro.ops.launch`` ...), the device name
scopes of each task's program and the two row counters, all opened
where the work happens in ``core/`` and ``relational/device_ops.py``.
They cost a call each and record nothing unless a profiler trace runs.
To trace a live ``Session``::

    import jax
    with jax.profiler.trace("/tmp/starling-trace"):
        session.submit("q5")

then open the ``.xplane.pb`` under ``/tmp/starling-trace/plugins/
profile/`` in TensorBoard's profiler, or pass
``create_perfetto_trace=True`` and load the ``perfetto_trace.json.gz``
at https://ui.perfetto.dev. The host spans sit on the lines of the
threads that opened them; the device's ops, named by their scope
(``join/radix_sort``), on the device's lines, on the same clock.

The package imports none of its modules: import each by its own name
(``repro.obs.trace``, ``repro.obs.spans``, ...), so that the query path
can import :mod:`repro.obs.spans` without pulling in the planner (which
``drift`` imports, and which imports the coordinator).
"""
