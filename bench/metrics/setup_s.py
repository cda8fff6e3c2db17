"""Seconds from the start of the process to the start of the window: importing
JAX, reaching the chip, making the fields, compiling (or reading the
compile cache) and warming every shape the window uses. Host clock."""

def read(run):
    return run.setup_s
