import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
from repro.launch.mesh import make_mesh
from repro.launch.steps import build_cell
from repro.configs.registry import list_cells, get_arch

mesh = make_mesh((2,2,2), ("pod","data","model"))
# the DeepSeek-V3 block (MLA, held experts) trains at tp 1 only
mesh_tp1 = make_mesh((2,4,1), ("pod","data","model"))
ok = bad = 0
for arch_id, shape in list_cells():
    cell = get_arch(arch_id).cell(shape)
    tp1 = getattr(get_arch(arch_id).config, "deepseek_block", False)
    try:
        plan = build_cell(arch_id, shape, mesh_tp1 if tp1 else mesh, smoke=True)
        lowered = plan.fn.lower(*plan.abstract_args)
        compiled = lowered.compile()
        print(f"OK   {arch_id:22s} {shape}")
        ok += 1
    except Exception as e:
        print(f"FAIL {arch_id:22s} {shape}: {type(e).__name__}: {str(e)[:200]}")
        bad += 1
print(f"\n{ok} ok, {bad} fail")
