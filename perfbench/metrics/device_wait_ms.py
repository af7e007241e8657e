"""Mean per query of the summed walls of the program's ``device.sync``
spans (each blocking device-to-host fetch: the host's wait for the
device, plus the transfer), in ms."""

from perfbench.program_spans import mean_span_ms


def read(run):
    return mean_span_ms(run, "device.sync")
