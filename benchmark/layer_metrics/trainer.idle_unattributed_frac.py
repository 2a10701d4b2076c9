"""Device-idle time of the traced window under no ``dtpu.trainer.*`` span
but ``epoch`` itself (or under none at all), over the window: idle that no
named piece of the loop's work explains. With ``idle_under_wait``,
``idle_at_fence`` and what lies under ``h2d`` and ``step`` (said on an earlier
line) it sums to the first device's ``device.idle_frac``. Nothing without a
traced epoch."""

from benchmark.harness import loop_capture

METRIC = {"layer": "trainer", "unit": "fraction", "source": "program_span",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    return loop_capture.idle_frac(observed.counters, "unattributed")
