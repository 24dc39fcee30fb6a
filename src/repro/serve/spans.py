"""The program's own profiler spans, by name (DESIGN.md §12).

Each span is a ``jax.profiler.TraceAnnotation`` on the host thread. While
a profile is being taken (``jax.profiler.start_trace``) it lands in the
same ``.xplane.pb`` as the device's ops, on the same clock, so a gap in
the device's timeline can be read against what the host was doing. With
no profile running a span costs about a microsecond, so spans sit at
layer boundaries, once per call, launch or request: never inside jitted
code (where they would fire only at trace time), never in per-word or
per-segment loops, and with no keyword metadata (paid even with the
profiler off).

The ``repro.stem.*`` spans never overlap one another. One
``repro.stem.launch`` opens per launch attempt; a launch that fails
closes its span too, so with no failure they count
``StemmerWorkload.ticks_launched``.
"""
from jax.profiler import TraceAnnotation as span

# Engine (serve/engine.py)
ENGINE_SUBMIT = "repro.engine.submit"   # Engine.submit
ENGINE_STEP = "repro.engine.step"       # Engine.step
# text front end (serve/text.py), inside repro.engine.submit
TEXT_FRONTEND = "repro.text.frontend"   # text -> word rows at admission
TEXT_FETCH = "repro.text.fetch"         # its blocking device->host reads
# StemmerWorkload's launch ring, inside repro.engine.step
STEM_COALESCE = "repro.stem.coalesce"   # pick a due retry or coalesce FIFO
STEM_STAGE = "repro.stem.stage"         # copy into the staging buffer
STEM_LAUNCH = "repro.stem.launch"       # megakernel call, D2H copies started
                                        # (and, once per handle shape, the
                                        # other bucket's warm launch)
STEM_FETCH = "repro.stem.fetch"         # D2H reads at retire (may block)
STEM_VERIFY = "repro.stem.verify"       # host checksum recompute + compare
STEM_SCATTER = "repro.stem.scatter"     # results back to the requests

ALL = (ENGINE_SUBMIT, ENGINE_STEP, TEXT_FRONTEND, TEXT_FETCH, STEM_COALESCE,
       STEM_STAGE, STEM_LAUNCH, STEM_FETCH, STEM_VERIFY, STEM_SCATTER)
