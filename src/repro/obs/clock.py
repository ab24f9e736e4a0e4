"""The serving stack's single wall-clock source.

Every serving-path timer — fleet-loop boundaries, cloud-fetch spans,
train/dryrun step timers, trace timestamps — reads this one helper, so
all spans share a monotonic timebase.  ``time.time()`` is wall-clock and
can step backwards under NTP adjustment; ``time.perf_counter()`` is
monotonic with the highest available resolution, which is what latency
spans need.  (Its epoch is arbitrary, so absolute values are only
meaningful as differences — exporters rebase against a recorder start.)
"""

from __future__ import annotations

import time

clock = time.perf_counter
