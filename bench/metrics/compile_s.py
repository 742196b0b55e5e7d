"""compile_s: seconds XLA spent compiling during set-up, from JAX's
backend-compile events; a program read from the persistent cache books
none."""


def read(r):
    return r.setup_compile["compile_s"]
