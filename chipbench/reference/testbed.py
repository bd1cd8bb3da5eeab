"""The cell model of arXiv:2202.11098, written down from the paper.

MobileNetV1 pool d0..d7 (Table III accuracy), local times anchored to
Table V (d1/d3 interpolated by MACs), one d0 variant each on the edge and
the cloud, 20 ms weak-link crossings (a weak end node pays 4 crossings;
a weak edge adds one crossing towards the edge, two towards the cloud),
busy CPU 1.3x and busy memory 1.1x, and k requests sharing the edge or
the cloud each taking k times the single-occupant time.
Actions 0..7 run d0..d7 on the end device, 8 offloads to the edge, 9 to
the cloud.
"""
import numpy as np

ACCURACY = np.array([89.9, 88.2, 84.9, 74.2, 88.9, 87.0, 83.2, 72.8])
T_LOCAL = np.array([517.2, 302.0, 142.3, 80.4, 269.8, 172.0, 111.8, 72.08])
T_EDGE = 269.8
T_CLOUD = 273.05
WEAK_S = 80.0
WEAK_E_EDGE = 20.0
WEAK_E_CLOUD = 40.0
BUSY_CPU = 1.30
BUSY_MEM = 1.10
N_MODELS = 8
N_ACTIONS = 10
A_EDGE, A_CLOUD = 8, 9
# edge and cloud run d0
ACC_MENU = np.concatenate([ACCURACY, [ACCURACY[0], ACCURACY[0]]])

# observation encoding (Table II state plus the round context, the fleet
# and edge-group loads and the constraint targets)
OCC_LEVELS = 8.0
LOAD_CAP = 8.0
ACC_NORM = 100.0
LATENCY_NORM = 1000.0
# the greedy baseline's feasibility slack, per remaining user (%)
ACC_TOL = 1e-2
