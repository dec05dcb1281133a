"""Device idle time in gaps of at least 20 us that fall inside one of
the program's ``repro.*`` host spans, per traced quantum."""
from harness import program_readings


def read(ctx):
    return program_readings.host_stall_ms(ctx)
