"""setup_s: seconds from the start of the benchmark's process to the window
opening: imports, reaching the chip, building the calls, compiling or
loading every program from the compilation cache, and one warm-up call of
each shape the window uses."""


def read(w):
    return w.setup_s
