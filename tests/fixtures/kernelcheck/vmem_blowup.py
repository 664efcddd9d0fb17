"""kernelcheck negative fixture: the memory check must fire.

Declares a kernel whose VMEM blocks grow quadratically with the server
axis — at the admissible ceiling the (m, m) carry block alone is
256 MiB, four times the kernels' scoped VMEM.  A correct contract would
either cap the axis or tile the block; this one does neither, so
``python -m repro.analysis.kernelcheck --modules <this file>`` must
exit 1 with a ``memory`` violation.
"""

from repro.analysis.contracts import contract, span


def _dispatch(geom):
    return "pallas"


def _vmem(geom):
    m = geom["m"]
    return {
        "busy/in": ((1, m), 4),
        "quadratic pairwise carry": ((m, m), 4),  # the blowup: m^2 words
        "take/out": ((1, m), 4),
    }


@contract(
    "fixture.vmem-blowup",
    axes=(span("m", 128, 8192, boundaries=(1024,)),),
    backends=("pallas",),
    dispatch=_dispatch,
    vmem=_vmem,
    notes="negative fixture: (m, m) block exceeds the VMEM budget",
)
def fake_kernel(busy, mu):
    raise NotImplementedError("fixture entry point is never executed")
