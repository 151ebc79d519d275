"""Seeded R17 violations: kernels without a stable `iotml_` name."""

from jax.experimental import pallas as pl

GOOD = "iotml_probe_kernel"
BAD = "probe_kernel"


def unnamed(kernel, shape, x):
    return pl.pallas_call(kernel, out_shape=shape)(x)  # line 10: R17


def foreign_prefix(kernel, shape, x):
    return pl.pallas_call(kernel, name="flash_fwd", out_shape=shape)(x)


def foreign_constant(kernel, shape, x):
    return pl.pallas_call(kernel, name=BAD, out_shape=shape)(x)


def computed(kernel, shape, x, which):
    return pl.pallas_call(kernel, name="iotml_" + which, out_shape=shape)(x)


def named_literal(kernel, shape, x):
    return pl.pallas_call(kernel, name="iotml_probe", out_shape=shape)(x)


def named_constant(kernel, shape, x):
    return pl.pallas_call(kernel, name=GOOD, out_shape=shape)(x)
