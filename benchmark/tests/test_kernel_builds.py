"""The kernel_builds.factor reader: the program's own kernel counter, and
None from a program that has none."""

import common


def _read():
    return common.Catalog().reader("kernel_builds.factor").read(None)


def test_reads_the_programs_built_kernels(monkeypatch):
    from capital_tpu.obs import spans

    fresh = spans.KernelCounter()
    monkeypatch.setattr(spans, "KERNELS", fresh)
    assert _read() == 0
    fresh.call()
    fresh.build()
    fresh.call()
    assert _read() == 1


def test_a_program_without_the_counter_reads_none(monkeypatch):
    from capital_tpu.obs import spans

    monkeypatch.delattr(spans, "KERNELS")
    assert _read() is None
