"""Pool pages the device allocator had handed out over pages reserved by
admission, summed over the traced quanta (the program's per-quantum page
counts)."""
from harness import program_readings


def read(ctx):
    return program_readings.kv_mapped_share(ctx)
