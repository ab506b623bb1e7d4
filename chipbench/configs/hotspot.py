"""HotSpot (Rodinia's 2-D thermal stencil) as a Lightning user runs it.

One iteration is one time step: one ``Context.launch`` of the stencil
kernel, with ``temp`` and ``out`` swapping roles between steps.  On a mesh
the rows are split over the chips (``StencilDist``) and each launch
exchanges one halo row with each neighbour.

This file holds everything the benchmark knows of the configuration: the
data made from the seed, the program's iteration, a plain float32
reference of its own, the control (that reference in bfloat16), and the
work one iteration needs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from spans import span

#: The Pallas kernel this configuration runs; its roofline metric is
#: ``hotspot_roofline``.
KERNEL = "hotspot"


def constants(cfg: dict) -> dict:
    """The step's constants as Rodinia's hotspot.c derives them from its chip
    (``cfg["rodinia"]``): the step over the cell's capacitance, the inverse
    resistances, and the ambient temperature."""
    r = cfg["rodinia"]
    cell = r["chip_m"] / r["grid"]  # square cells
    cap = r["factor_chip"] * r["spec_heat_si"] * r["t_chip_m"] * cell * cell
    resist_xy = cell / (2.0 * r["k_si"] * r["t_chip_m"] * cell)
    resist_z = r["t_chip_m"] / (r["k_si"] * cell * cell)
    max_slope = r["max_pd_w_m2"] / (r["factor_chip"] * r["t_chip_m"]
                                   * r["spec_heat_si"])
    step = r["precision"] / max_slope
    return {"sdc": step / cap, "rx": 1.0 / resist_xy, "ry": 1.0 / resist_xy,
            "rz": 1.0 / resist_z, "amb": r["amb_temp"]}


def max_power(cfg: dict) -> float:
    """Largest power of one cell (W): Rodinia's peak density times its area."""
    r = cfg["rodinia"]
    return r["max_pd_w_m2"] * (r["chip_m"] / r["grid"]) ** 2

ANNOTATION = ("global [i, j] => read temp[i-1:i+1, j-1:j+1], "
              "read power[i,j], write out[i,j]")


def shape(cfg: dict, chips: int) -> tuple[int, int]:
    return cfg["rows_per_chip"] * chips, cfg["cols"]


def work(cfg: dict, traffic: dict, chips: int) -> dict:
    """FLOPs and HBM bytes of one time step on one chip.  Each cell reads
    temp and power once and writes out once; its update takes 15 flops
    (two neighbour sums less twice the centre and their scaling, 4 each;
    the ambient term, 2; the three sums with power, 3; the step, 2)."""
    cells = cfg["rows_per_chip"] * cfg["cols"]
    one = {"flops": 15.0 * cells, "bytes": 3.0 * 4 * cells}
    return {"kernel": one, "step": dict(one)}


# --------------------------------------------------------------------------
# Data: row r of temp and power depends on (seed, r) alone, so any block of
# rows can be made again on any device.
# --------------------------------------------------------------------------


def _rows_fn(key, lo, temp_k, power_max, rows: int, cols: int,
             dtype=jnp.float32):
    kt, kp = jax.random.split(key)
    idx = lo + jnp.arange(rows)

    def row(k, i):
        return jax.random.uniform(jax.random.fold_in(k, i), (cols,))

    temp = temp_k[0] + (temp_k[1] - temp_k[0]) * jax.vmap(
        functools.partial(row, kt))(idx)
    power = power_max * jax.vmap(functools.partial(row, kp))(idx) ** 2
    return temp.astype(dtype), power.astype(dtype)


_rows = jax.jit(_rows_fn, static_argnames=("rows", "cols", "dtype"))


def make_data(cfg: dict, seed: int, rows: int, sharding=None):
    args = (jax.random.key(seed), 0, jnp.asarray(cfg["temp_k"]),
            max_power(cfg), rows, cfg["cols"])
    if sharding is None:
        return _rows(*args)
    gen = jax.jit(_rows_fn, static_argnums=(4, 5),
                  out_shardings=(sharding, sharding))
    return gen(*args)


# --------------------------------------------------------------------------
# The program under test: Context.launch of the Pallas stencil.
# --------------------------------------------------------------------------


def _body(views, info, chips: int, consts: dict):
    """The launch's kernel body: the stencil on this chip's rows."""
    from repro.kernels import hotspot_step

    temp, power = views["temp"], views["power"]
    up = down = None
    if temp.shape[0] != power.shape[0]:
        # Mesh: one halo row each side, zero outside the grid; the outer
        # chips clamp to their own edge row instead.
        up = jnp.where(info.device_index == 0, temp[1:2], temp[:1])
        down = jnp.where(info.device_index == chips - 1, temp[-2:-1],
                         temp[-1:])
        temp = temp[1:-1]
    return {"out": hotspot_step(temp, power, up=up, down=down, **consts)}


class Program:
    """The cell's state and its iteration, built from the seed."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, devices,
                 tracer=None):
        from repro.core import Context, KernelDef, RowDist, StencilDist

        chips = len(devices)
        self.devices = devices
        self.grid = shape(cfg, chips)
        rows, cols = self.grid
        if chips > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P

            mesh = jax.make_mesh((chips,), ("data",), devices=devices)
            self.ctx = Context(mesh=mesh, tracer=tracer)
            temp, power = make_data(cfg, seed, rows,
                                    NamedSharding(mesh, P("data", None)))
            dists = (StencilDist(rows // chips, 1), RowDist(), RowDist())
        else:
            self.ctx = Context(tracer=tracer)
            with jax.default_device(devices[0]):
                temp, power = make_data(cfg, seed, rows)
            dists = (None, None, None)
        ctx = self.ctx
        self.temp = ctx.array(temp, dist=dists[0], name="temp")
        self.power = ctx.array(power, dist=dists[1], name="power")
        self.out = ctx.array(jnp.zeros_like(temp), dist=dists[2], name="out")
        self.kernel = KernelDef.define(
            KERNEL, functools.partial(_body, chips=chips,
                                      consts=constants(cfg)), ANNOTATION)

    def step(self) -> jax.Array:
        with span("launch"):
            res = self.ctx.launch(self.kernel, grid=self.grid, args={
                "temp": self.temp, "power": self.power, "out": self.out})
        self.temp, self.out = (self.temp.replace_value(res["out"].value),
                               self.out.replace_value(self.temp.value))
        return self.temp.value

    def check(self, steps: int) -> list[jax.Array]:
        """Run the first ``steps`` steps; the temperature after them, one
        block of rows per chip, stays on the device for the comparison."""
        for _ in range(steps):
            self.step()
        return jax.block_until_ready(program_blocks(self.temp.value,
                                                    self.devices))

    def free(self) -> None:
        self.temp = self.power = self.out = self.ctx = self.kernel = None


# --------------------------------------------------------------------------
# Reference and comparison.
# --------------------------------------------------------------------------


def ref_step(temp, power, sdc, rx, ry, rz, amb):
    """One step with zero-flux edges: a neighbour outside the grid takes
    the cell's own value."""
    q = jnp.pad(temp, 1, mode="edge")
    c = q[1:-1, 1:-1]
    delta = sdc * ((q[1:-1, :-2] + q[1:-1, 2:] - 2 * c) * rx
                   + (q[:-2, 1:-1] + q[2:, 1:-1] - 2 * c) * ry
                   + (amb - c) * rz + power)
    return c + delta


@functools.partial(jax.jit, static_argnames=("rows", "cols", "steps",
                                             "dtype"))
def _ref_block(key, lo, temp_k, power_max, consts, rows: int, cols: int,
               steps: int, dtype):
    temp, power = _rows_fn(key, lo, temp_k, power_max, rows, cols, dtype)
    consts = {k: jnp.asarray(v, dtype) for k, v in consts.items()}
    for _ in range(steps):
        temp = ref_step(temp, power, **consts)
    return temp.astype(jnp.float32)


def _ref_blocks(cfg, seed, devices, steps, dtype):
    """The reference's temperature after ``steps``, one block of rows per
    chip, each made on that chip from the seed with ``steps`` extra rows on
    each inner side (a cut edge spoils one row per step)."""
    rows, cols = shape(cfg, len(devices))
    per = cfg["rows_per_chip"]
    key = jax.random.key(seed)
    out = []
    for b, dev in enumerate(devices):
        lo, hi = b * per, (b + 1) * per
        elo, ehi = max(lo - steps, 0), min(hi + steps, rows)
        with jax.default_device(dev):
            t = _ref_block(key, elo, jnp.asarray(cfg["temp_k"]),
                           max_power(cfg), constants(cfg), ehi - elo, cols,
                           steps, dtype)
            out.append(t[lo - elo:lo - elo + per])
    return out


def program_blocks(result: jax.Array, devices) -> list[jax.Array]:
    """The program's result as one block of rows per chip, in chip order."""
    if len(devices) == 1:
        return [result]
    shards = sorted(result.addressable_shards, key=lambda s: s.index[0].start)
    return [s.data for s in shards]


def readings(cfg: dict, traffic: dict, seed: int, devices,
             blocks: list[jax.Array]) -> dict:
    """Numbers compared against their limits: ``temp_err``, the largest gap
    in kelvin between ``blocks`` and the float32 reference after the
    checked steps."""
    steps = cfg["check_steps"]
    ref = _ref_blocks(cfg, seed, devices, steps, jnp.float32)
    return {"temp_err": max(float(jnp.max(jnp.abs(g - r)))
                            for g, r in zip(blocks, ref))}


def control(cfg: dict, traffic: dict, seed: int, devices):
    """The control: the reference computed in bfloat16, in the program's
    place."""
    return _ref_blocks(cfg, seed, devices, cfg["check_steps"], jnp.bfloat16)
