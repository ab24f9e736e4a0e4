"""Host spans on the profiler's clock.

``Observability.span(name, **args)`` times one host phase of the serving
loop (a window boundary's admission, dispatch, sync, harvest).  On, a span

  * enters ``jax.profiler.TraceAnnotation(name)``, so a running profiler
    records it in its host plane on the same clock as the device's
    operations (with no profiler running the annotation is a no-op);
  * observes its milliseconds into the registry histogram ``span_ms``
    labelled ``span`` (key ``span_ms{span="<name>"}``);
  * with a ``TraceRecorder`` attached, writes itself to the recorder's
    ``host`` track.

Producers without a handle use ``NULL_SPAN``: one shared object, so a
disabled span allocates nothing and reads no clock.  ``set(**args)``
attaches arguments known only inside the span; guard it with ``if span:``
(``NULL_SPAN`` is falsy) to keep the disabled path free of the keyword
dict.  No span reads the device, so none adds a host-device sync.
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation

from repro.obs.clock import clock

HOST_TRACK = "host"


class _NullSpan:
    """The disabled span: enter, exit and ``set`` do nothing."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def __bool__(self) -> bool:
        return False

    def set(self, **args) -> None:
        pass


NULL_SPAN = _NullSpan()


class Span:
    """One enabled host span (single use)."""

    __slots__ = ("name", "args", "_metrics", "_trace", "_ann", "_t0")

    def __init__(self, metrics, trace, name: str, args: dict):
        self.name = name
        self.args = args
        self._metrics = metrics
        self._trace = trace
        self._ann = None
        self._t0 = 0.0

    def set(self, **args) -> None:
        """Attach ``args`` to the open span (profiler event and track)."""

        self.args.update(args)
        self._ann.set_metadata(**args)

    def __enter__(self) -> "Span":
        self._ann = TraceAnnotation(self.name, **self.args)
        self._ann.__enter__()
        self._t0 = clock()
        return self

    def __exit__(self, *exc) -> None:
        t1 = clock()
        self._ann.__exit__(*exc)
        self._metrics.histogram("span_ms", span=self.name).observe(
            (t1 - self._t0) * 1e3)
        if self._trace is not None:
            self._trace.complete(HOST_TRACK, self.name, self._t0, t1,
                                 self.args or None)
