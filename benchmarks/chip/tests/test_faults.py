"""The comparison that decides ``correct`` fails when the timed path is
broken underneath a run: the control (a broken guarantee) and each fault
a single-chip cell can have.  The chip guard is bypassed here only."""
import numpy as np
import pytest

import harness
import run
import tiny


def _driver(cell):
    return harness.load_module(cell.driver_path, cell.traffic["kind"]).Driver


def _run(cell, driver_cls=None, control=False):
    result, checks = run.run(cell, tiny.SEED, 1.0, False,
                             check_device=False, driver_cls=driver_cls,
                             control=control)
    return result, checks


# ------------------------------------------------------------------ compress

def _compress_fault(cell, kind):
    base = _driver(cell)

    class Faulty(base):
        def _publish(self, i, path):
            from repro.core import hybrid
            from repro.io import TACZWriter

            res = hybrid.compress_amr(self.datasets[i], eb=self.eb_used,
                                      unit=int(self.cfg["unit"]))
            levels = res.levels
            if kind == "altered":
                # one code of one brick changed where the compressor
                # produced it, to the symbol of its stream farthest away
                for r in levels[0].artifacts.results:
                    far = r.codes[np.argmax(np.abs(r.codes - r.codes[0]))]
                    if abs(int(far) - int(r.codes[0])) >= 2:
                        r.codes[0] = far
                        break
            elif kind == "half":
                levels = levels[:len(levels) // 2]
            with TACZWriter(path, eb=self.eb_used) as w:
                for lr in levels:
                    w.add_compressed(lr)
            import os
            return os.path.getsize(path)

    return Faulty


@pytest.mark.parametrize("fault", ["control", "altered", "half"])
def test_compress_fault_is_not_correct(fault, tmp_path, monkeypatch):
    c = tiny.cell("nyx_z10.compress", tmp_path, monkeypatch)
    if fault == "control":
        result, checks = _run(c, control=True)
        assert checks["max_err_over_eb"]["value"] > 3.0
    else:
        result, checks = _run(c, _compress_fault(c, fault))
    assert not result["correct"], checks


# --------------------------------------------------------------------- serve

def _serve_fault(cell, kind):
    base = _driver(cell)

    class Faulty(base):
        def _start(self):
            super()._start()
            srv = self.httpd.region_server
            real = srv.get_regions_with_crc

            def broken(boxes, levels=None, **kw):
                crc, out = real(boxes, levels, **kw)
                for per_box in out:
                    if kind == "altered":
                        d = per_box[0].data
                        if d.size:
                            d = d.copy()
                            d.flat[d.size // 2] += np.float32(1.0)
                            per_box[0].data = d
                    elif kind == "half":
                        del per_box[len(per_box) // 2:]
                return crc, out

            srv.get_regions_with_crc = broken

    return Faulty


@pytest.mark.parametrize("fault", ["control", "altered", "half"])
def test_serve_fault_is_not_correct(fault, tmp_path, monkeypatch):
    c = tiny.cell("nyx_z10.serve_cold", tmp_path, monkeypatch)
    if fault == "control":
        result, checks = _run(c, control=True)
        assert checks["crops_differ"]["value"] > 0
    else:
        result, checks = _run(c, _serve_fault(c, fault))
    assert not result["correct"], checks
