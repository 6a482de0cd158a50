"""ExecutionSpec: ONE frozen value that names how a Program executes.

The old run surface was kwarg sprawl — ``Program.run(ext, engine=,
nu_kernel=, interpret=, sharded=, mesh=)`` — five orthogonal-looking
knobs that were not orthogonal at all (``nu_kernel`` only meant
something on the jax engine, ``mesh`` only under ``sharded=True``,
``interpret=None`` resolved to a platform default in three different
places). :class:`ExecutionSpec` replaces all of them:

* ``engine``    — ``"jax"`` (compiled batched), ``"python"`` (per-op
  reference executor), ``"oracle"`` (dense integer LIF);
* ``kernel``    — the jax engine's kernel tier: ``"fused"`` (the
  route/accumulate/Neuron-Unit Pallas megakernel,
  :mod:`repro.kernels.fused_step`), ``"lif"`` (segment-sum synaptic
  phase + the small Pallas LIF kernel), ``"reference"`` (segment-sum +
  pure-jnp LIF). ``None`` resolves to the platform default;
* ``interpret`` — Pallas interpret mode; ``None`` resolves to the
  platform default (True off-TPU);
* ``mesh``      — ``None`` runs single-device; a jax ``Mesh`` (or the
  string ``"auto"`` = every device on the ``data`` axis) data-shards
  the batch through the owned :class:`~repro.serve.sharded
  .ShardedRunner`;
* ``donate``    — donate the membrane/spike state buffers to the
  compiled call (XLA reuses their storage for the outputs).

:meth:`resolve` folds the platform defaults in ONCE and validates the
combination; the **resolved** spec is hashable and is the engine/runner
cache key in ``Program.engine()`` / ``Program.sharded_runner()`` — so
an explicit value and the default it resolves to always share one
compiled engine. All three kernel tiers are bit-exact (deterministic-
commit property): the spec selects a speed/feature point, never a
numerical behavior.
"""
from __future__ import annotations

import dataclasses
import warnings

ENGINES = ("jax", "python", "oracle")
KERNELS = ("fused", "lif", "reference")

AUTO_MESH = "auto"


def default_kernel() -> str:
    """Platform-default kernel tier for the jax engine.

    ``"fused"`` everywhere: the megakernel targets the TPU dataflow
    (one launch per timestep), and in interpret mode on CPU it
    resolves to ONE full-array tile — a single XLA dot + epilogue —
    which matches the split pipeline at toy scale and beats it ~4x on
    the paper-scale SHD instance (see
    ``benchmarks/kernel_benchmarks.py`` tier rows).
    """
    return "fused"


def default_interpret() -> bool:
    """Platform-default Pallas mode: compiled on a TPU, the interpreter
    elsewhere (the CPU test suite). Only :meth:`ExecutionSpec.resolve`
    applies it; the kernels themselves take ``interpret`` explicitly,
    and ``chip_smoke.py`` asserts the resolved spec compiled."""
    import jax
    return jax.default_backend() != "tpu"


@dataclasses.dataclass(frozen=True)
class ExecutionSpec:
    """How to execute a compiled :class:`~repro.core.program.Program`."""
    engine: str = "jax"
    kernel: str | None = None          # jax only; None -> platform default
    interpret: bool | None = None      # jax only; None -> platform default
    mesh: object | None = None         # jax only; None | Mesh | "auto"
    donate: bool = False               # jax only

    def __post_init__(self):
        if self.engine not in ENGINES:
            raise ValueError(f"unknown engine {self.engine!r}; use one of "
                             f"{ENGINES}")
        if self.kernel is not None and self.kernel not in KERNELS:
            raise ValueError(f"unknown kernel {self.kernel!r}; use one of "
                             f"{KERNELS} (or None for the platform default)")
        if self.engine != "jax":
            if (self.kernel is not None or self.interpret is not None
                    or self.donate):
                raise ValueError(
                    f"kernel/interpret/donate select jax-engine build "
                    f"options; they do not apply to engine={self.engine!r}")
            if self.mesh is not None:
                raise ValueError(f"mesh= shards the jax engine; got "
                                 f"engine={self.engine!r}")

    # -- derived views -------------------------------------------------------

    @property
    def sharded(self) -> bool:
        """True iff this spec routes through a multi-device mesh."""
        return self.mesh is not None

    @property
    def resolved(self) -> bool:
        """True iff no field still names a platform default."""
        if self.engine != "jax":
            return True
        return (self.kernel is not None and self.interpret is not None
                and not isinstance(self.mesh, str))

    def single_device(self) -> "ExecutionSpec":
        """This spec without the mesh — the per-device engine key the
        sharded runner (and its small-batch fallback) builds from."""
        if self.mesh is None:
            return self
        return dataclasses.replace(self, mesh=None)

    # -- resolution ----------------------------------------------------------

    def resolve(self) -> "ExecutionSpec":
        """Fold platform defaults in; validation happened at init.

        Idempotent, and the ONLY place defaults are decided: the
        resolved spec is what engines/runners are keyed on, so
        ``ExecutionSpec()`` and ``ExecutionSpec(kernel="fused",
        interpret=<platform>)`` share one compiled engine.
        """
        if self.engine != "jax":
            return self
        kernel = self.kernel if self.kernel is not None else default_kernel()
        interpret = (default_interpret() if self.interpret is None
                     else bool(self.interpret))
        mesh = self.mesh
        if isinstance(mesh, str):
            if mesh != AUTO_MESH:
                raise ValueError(f"mesh={mesh!r}: the only string form is "
                                 f"{AUTO_MESH!r} (every device on 'data')")
            from repro.launch.mesh import make_serving_mesh
            mesh = make_serving_mesh()
        return dataclasses.replace(self, kernel=kernel, interpret=interpret,
                                   mesh=mesh)


def as_spec(spec: "ExecutionSpec | str | None",
            default_engine: str = "jax") -> ExecutionSpec:
    """Coerce the ``spec`` argument of the run surface.

    ``None`` -> the artifact's default engine; a string is shorthand
    for ``ExecutionSpec(engine=<string>)`` so the common
    ``program.run(ext, "python")`` stays one token.
    """
    if spec is None:
        return ExecutionSpec(engine=default_engine)
    if isinstance(spec, str):
        return ExecutionSpec(engine=spec)
    if not isinstance(spec, ExecutionSpec):
        raise TypeError(f"spec must be an ExecutionSpec, engine-name "
                        f"string, or None; got {type(spec).__name__}")
    return spec


# ---------------------------------------------------------------------------
# Legacy-kwarg shim: the deprecated Program.run(engine=, nu_kernel=,
# interpret=, sharded=, mesh=) surface delegates here.
# ---------------------------------------------------------------------------

_NU_KERNEL_TIER = {True: "lif", False: "reference"}


def spec_from_legacy_kwargs(*, engine=None, nu_kernel=None, interpret=None,
                            sharded=None, mesh=None, default_engine="jax",
                            where="Program.run", stacklevel=3
                            ) -> ExecutionSpec:
    """Map the pre-ExecutionSpec kwargs onto a spec, warning once.

    Preserves the old semantics exactly: ``nu_kernel=True`` was the
    segment-sum + Pallas-LIF pipeline (now the ``"lif"`` tier),
    ``nu_kernel=False`` the pure-jnp step (now ``"reference"``);
    ``sharded=True`` with no mesh meant the default serving mesh, and
    ``sharded=True`` with a non-jax engine was an error with this exact
    message.
    """
    passed = {k: v for k, v in [("engine", engine), ("nu_kernel", nu_kernel),
                                ("interpret", interpret),
                                ("sharded", sharded), ("mesh", mesh)]
              if v is not None}
    warnings.warn(
        f"{where}({', '.join(f'{k}=' for k in passed)}) is deprecated; "
        f"pass ExecutionSpec(engine=, kernel=, interpret=, mesh=, donate=) "
        f"instead (see README 'Migration to ExecutionSpec')",
        DeprecationWarning, stacklevel=stacklevel)
    sharded = bool(sharded)
    if sharded:
        engine = engine or "jax"
        if engine != "jax":
            raise ValueError(f"sharded=True runs the jax engine; got "
                             f"engine={engine!r}")
        mesh = mesh if mesh is not None else AUTO_MESH
    elif mesh is not None:
        mesh = None                     # old API: mesh ignored unless sharded
    engine = engine or default_engine
    if engine != "jax":
        return ExecutionSpec(engine=engine)
    return ExecutionSpec(
        engine="jax",
        kernel=None if nu_kernel is None else _NU_KERNEL_TIER[bool(nu_kernel)],
        interpret=interpret, mesh=mesh)
