"""Blocking device->host fetches per request (``materialize`` of a device
value in mid-request: the host waits for the device, then the device for the
host)."""

import program_spans


def read(obs):
    return program_spans.mean_of(obs, "host_syncs")
