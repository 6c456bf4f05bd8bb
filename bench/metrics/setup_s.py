"""setup_s: seconds from process start to the start of the measured window
(imports, device init, shapes, compiles or cache loads, weights, the first
steps).  Host clock."""


def read(rec):
    return rec.setup_s
