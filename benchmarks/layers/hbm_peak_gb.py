"""Allocator peak on the fullest chip, in GB (1e9 bytes). An unknown
device kind has no capacity to read it against and is refused earlier."""



def read(run):
    return run["memory_peak_bytes"] / 1e9
