"""setup_s: seconds from process start to the first timed operation:
building and loading the catalog, the upload to the device, the planes
and the warm-up (compiles or compile-cache reads included)."""


def read(rec):
    return rec.setup_s
