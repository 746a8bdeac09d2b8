"""CPU rehearsal of ``chip_smoke.py --chips 4`` at smoke size: the fleet,
dataframe and kind-pods phases over emulated host devices."""
import importlib.util
import os
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
spec = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
chip_smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(chip_smoke)

fleet = chip_smoke.fleet_phase(smoke=True)
print("fleet devices:", [d.id for d in fleet["fleet"]["engine_devices"]])
print("dataframe:", chip_smoke.dataframe_phase(smoke=True))
with tempfile.TemporaryDirectory() as ckpt:
    pods = chip_smoke.kind_pods_phase(ckpt, smoke=True)
print("kind-pods DL devices:", pods["dl_pod_devices"],
      "state on:", pods["kind_pods"]["state_devices"])
print("CHIP SMOKE FOUR-CHIP PHASES PASS")
