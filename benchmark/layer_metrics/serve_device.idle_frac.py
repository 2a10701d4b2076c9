"""Share of the traced seconds of the serving window in which no operation
ran on the device: how far engine, protocol and host hold the chip back."""

METRIC = {"layer": "device", "unit": "fraction", "source": "device_trace",
          "moves": "serve_goodput_per_s_per_chip"}


def read(observed):
    return observed.trace.idle_frac() if observed.trace else None
