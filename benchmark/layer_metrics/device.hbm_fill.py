"""Peak bytes in use as a percent of what the backend lets a program
allocate on the chip (layer: memory)."""

from benchmark.readers import ratio


def read(obs):
    return ratio(obs, "device.peak_hbm_bytes", "device.hbm_bytes_limit", 100.0)
