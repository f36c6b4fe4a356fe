"""Peak bytes in use on the fullest chip, as the backend reports them
(layer: memory). A guard: a cell that stops filling the chip is another
cell."""

from benchmark.readers import counter


def read(obs):
    return counter(obs, "device.peak_hbm_bytes")
