"""Wire-level telemetry: traces, metrics and bandwidth probes.

Host-side observability for the train and serve loops.  Everything here
runs OUTSIDE the jit'd programs — instrumentation sites read static facts
at trace time (``eval_shape`` payload structs, codec names) and wall
clocks around the jit'd calls, so the telemetry layer adds ZERO device
ops and is free when disabled (the default).

  trace.py    span/counter API over a host-side ring buffer; ``scope``
              names a function's ops in the compiled program
              (metadata only)
  export.py   JSONL exporter, event schema
  quality.py  per-boundary compression error / feedback-norm debug tap
  probes.py   achieved-bytes/s link probes feeding PolicyRules
"""
from repro.obs.trace import (Tracer, disable, enable, get_tracer,  # noqa: F401
                             instant, counter, span)
from repro.obs.export import (EVENT_SCHEMA, to_jsonl,  # noqa: F401
                              validate_events, validate_jsonl)
