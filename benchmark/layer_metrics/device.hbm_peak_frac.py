"""Peak device memory over what the device offers, on the fullest chip:
``memory_stats()`` ``peak_bytes_in_use`` / ``bytes_limit``. Sizing: a cell
that leaves most of the memory empty is too small to stand for a job."""

METRIC = {"layer": "device", "unit": "fraction", "source": "program_counter",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    limit = observed.device.get("memory_limit_bytes")
    return observed.device["memory_peak_bytes"] / limit if limit else None
