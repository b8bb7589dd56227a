"""Whole-image GLCMs: one answer of (n_offsets, 14) Haralick features per
request, over uint8 images binned over each image's own range (the
paper's Table III). The pool is ``chipbench.data``'s, the reference and
its comparison ``chipbench.reference``'s, the work
``chipbench.roofline``'s."""

from __future__ import annotations

from chipbench import data, kinds, roofline
from chipbench import reference as ref

CPU_SHAPE = [48, 40]


def make_pool(pool, shape, seed):
    return data.make_pool(pool, shape, seed)


def build_engine(cell, config):
    return kinds.serve_engine(cell, config, features=True)


def work(cell, config, pool):
    spec = config["spec"]
    return roofline.work(cell["shape"], ref.offsets(spec["pairs"], spec["ndim"]),
                         spec["levels"], pool[0].dtype.itemsize)


def reference(raw, config):
    return ref.answer(ref.raw_counts(raw, config), config)


def error(got, want):
    return ref.feature_error(got, want)


def worst(got, want):
    k, f = ref.worst_entry(got, want)
    return f"offset {k}, {ref.FEATURE_NAMES[f]}"


def cpu_cell(cell, config):
    return dict(cell, shape=CPU_SHAPE), "onehot"
