"""Host-device transfer, reference tables: gigabytes a second that the
reference-table upload put on the device over the window
(``ComputingStats.upload_bytes`` over ``upload_s``, both summed inside
the ``compute.upload`` span).  None where the program keeps no such
counter or uploaded nothing."""


def read(ctx):
    nbytes = ctx.window.get("upload_bytes", 0)
    secs = ctx.window.get("upload_s", 0)
    if nbytes <= 0 or secs <= 0:
        return None
    return nbytes / secs / 1e9
