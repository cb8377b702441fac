# -*- coding: utf-8 -*-
# Generated by the protocol buffer compiler.  DO NOT EDIT!
# source: scheduler.proto
# (protoc --python_out=. scheduler.proto, from this directory: the image
# has protoc for messages and no grpc_python_plugin; scheduler.proto is the
# authoritative schema and tests/test_agent_spans.py holds the two equal)
"""Generated protocol buffer code."""
from google.protobuf.internal import builder as _builder
from google.protobuf import descriptor as _descriptor
from google.protobuf import descriptor_pool as _descriptor_pool
from google.protobuf import symbol_database as _symbol_database
# @@protoc_insertion_point(imports)

_sym_db = _symbol_database.Default()




DESCRIPTOR = _descriptor_pool.Default().AddSerializedFile(b'\n\x0fscheduler.proto\x12\x0bk8sschedtpu\"I\n\x18LabelSelectorRequirement\x12\x0b\n\x03key\x18\x01 \x01(\t\x12\x10\n\x08operator\x18\x02 \x01(\t\x12\x0e\n\x06values\x18\x03 \x03(\t\"\xc8\x01\n\rLabelSelector\x12\x41\n\x0cmatch_labels\x18\x01 \x03(\x0b\x32+.k8sschedtpu.LabelSelector.MatchLabelsEntry\x12@\n\x11match_expressions\x18\x02 \x03(\x0b\x32%.k8sschedtpu.LabelSelectorRequirement\x1a\x32\n\x10MatchLabelsEntry\x12\x0b\n\x03key\x18\x01 \x01(\t\x12\r\n\x05value\x18\x02 \x01(\t:\x02\x38\x01\"\x91\x01\n\x10NodeSelectorTerm\x12@\n\x11match_expressions\x18\x01 \x03(\x0b\x32%.k8sschedtpu.LabelSelectorRequirement\x12;\n\x0cmatch_fields\x18\x02 \x03(\x0b\x32%.k8sschedtpu.LabelSelectorRequirement\"\\\n\x17PreferredSchedulingTerm\x12\x0e\n\x06weight\x18\x01 \x01(\x05\x12\x31\n\npreference\x18\x02 \x01(\x0b\x32\x1d.k8sschedtpu.NodeSelectorTerm\"x\n\x0cNodeAffinity\x12/\n\x08required\x18\x01 \x03(\x0b\x32\x1d.k8sschedtpu.NodeSelectorTerm\x12\x37\n\tpreferred\x18\x02 \x03(\x0b\x32$.k8sschedtpu.PreferredSchedulingTerm\"o\n\x0fPodAffinityTerm\x12\x32\n\x0elabel_selector\x18\x01 \x01(\x0b\x32\x1a.k8sschedtpu.LabelSelector\x12\x14\n\x0ctopology_key\x18\x02 \x01(\t\x12\x12\n\nnamespaces\x18\x03 \x03(\t\"U\n\x17WeightedPodAffinityTerm\x12\x0e\n\x06weight\x18\x01 \x01(\x05\x12*\n\x04term\x18\x02 \x01(\x0b\x32\x1c.k8sschedtpu.PodAffinityTerm\"v\n\x0bPodAffinity\x12.\n\x08required\x18\x01 \x03(\x0b\x32\x1c.k8sschedtpu.PodAffinityTerm\x12\x37\n\tpreferred\x18\x02 \x03(\x0b\x32$.k8sschedtpu.WeightedPodAffinityTerm\"\xa1\x01\n\x08\x41\x66\x66inity\x12\x30\n\rnode_affinity\x18\x01 \x01(\x0b\x32\x19.k8sschedtpu.NodeAffinity\x12.\n\x0cpod_affinity\x18\x02 \x01(\x0b\x32\x18.k8sschedtpu.PodAffinity\x12\x33\n\x11pod_anti_affinity\x18\x03 \x01(\x0b\x32\x18.k8sschedtpu.PodAffinity\"J\n\nToleration\x12\x0b\n\x03key\x18\x01 \x01(\t\x12\x10\n\x08operator\x18\x02 \x01(\t\x12\r\n\x05value\x18\x03 \x01(\t\x12\x0e\n\x06\x65\x66\x66\x65\x63t\x18\x04 \x01(\t\"3\n\x05Taint\x12\x0b\n\x03key\x18\x01 \x01(\t\x12\r\n\x05value\x18\x02 \x01(\t\x12\x0e\n\x06\x65\x66\x66\x65\x63t\x18\x03 \x01(\t\"\x92\x01\n\x18TopologySpreadConstraint\x12\x10\n\x08max_skew\x18\x01 \x01(\x05\x12\x14\n\x0ctopology_key\x18\x02 \x01(\t\x12\x1a\n\x12when_unsatisfiable\x18\x03 \x01(\t\x12\x32\n\x0elabel_selector\x18\x04 \x01(\x0b\x32\x1a.k8sschedtpu.LabelSelector\"]\n\rContainerPort\x12\x16\n\x0e\x63ontainer_port\x18\x01 \x01(\x05\x12\x11\n\thost_port\x18\x02 \x01(\x05\x12\x10\n\x08protocol\x18\x03 \x01(\t\x12\x0f\n\x07host_ip\x18\x04 \x01(\t\"\xbc\x01\n\tContainer\x12\x0c\n\x04name\x18\x01 \x01(\t\x12\r\n\x05image\x18\x02 \x01(\t\x12\x36\n\x08requests\x18\x03 \x03(\x0b\x32$.k8sschedtpu.Container.RequestsEntry\x12)\n\x05ports\x18\x04 \x03(\x0b\x32\x1a.k8sschedtpu.ContainerPort\x1a/\n\rRequestsEntry\x12\x0b\n\x03key\x18\x01 \x01(\t\x12\r\n\x05value\x18\x02 \x01(\t:\x02\x38\x01\"\xad\x02\n\nObjectMeta\x12\x0c\n\x04name\x18\x01 \x01(\t\x12\x11\n\tnamespace\x18\x02 \x01(\t\x12\x0b\n\x03uid\x18\x03 \x01(\t\x12\x33\n\x06labels\x18\x04 \x03(\x0b\x32#.k8sschedtpu.ObjectMeta.LabelsEntry\x12=\n\x0b\x61nnotations\x18\x05 \x03(\x0b\x32(.k8sschedtpu.ObjectMeta.AnnotationsEntry\x12\x1a\n\x12\x63reation_timestamp\x18\x06 \x01(\x01\x1a-\n\x0bLabelsEntry\x12\x0b\n\x03key\x18\x01 \x01(\t\x12\r\n\x05value\x18\x02 \x01(\t:\x02\x38\x01\x1a\x32\n\x10\x41nnotationsEntry\x12\x0b\n\x03key\x18\x01 \x01(\t\x12\r\n\x05value\x18\x02 \x01(\t:\x02\x38\x01\"\xcc\x04\n\x07PodSpec\x12*\n\ncontainers\x18\x01 \x03(\x0b\x32\x16.k8sschedtpu.Container\x12\x11\n\tnode_name\x18\x02 \x01(\t\x12=\n\rnode_selector\x18\x03 \x03(\x0b\x32&.k8sschedtpu.PodSpec.NodeSelectorEntry\x12\'\n\x08\x61\x66\x66inity\x18\x04 \x01(\x0b\x32\x15.k8sschedtpu.Affinity\x12,\n\x0btolerations\x18\x05 \x03(\x0b\x32\x17.k8sschedtpu.Toleration\x12J\n\x1btopology_spread_constraints\x18\x06 \x03(\x0b\x32%.k8sschedtpu.TopologySpreadConstraint\x12\x10\n\x08priority\x18\x07 \x01(\x05\x12\x1b\n\x13priority_class_name\x18\x08 \x01(\t\x12\x19\n\x11preemption_policy\x18\t \x01(\t\x12\x16\n\x0escheduler_name\x18\n \x01(\t\x12\x34\n\x08overhead\x18\x0b \x03(\x0b\x32\".k8sschedtpu.PodSpec.OverheadEntry\x12\x11\n\tpod_group\x18\x0c \x01(\t\x12\x0f\n\x07volumes\x18\r \x03(\t\x1a\x33\n\x11NodeSelectorEntry\x12\x0b\n\x03key\x18\x01 \x01(\t\x12\r\n\x05value\x18\x02 \x01(\t:\x02\x38\x01\x1a/\n\rOverheadEntry\x12\x0b\n\x03key\x18\x01 \x01(\t\x12\r\n\x05value\x18\x02 \x01(\t:\x02\x38\x01\"q\n\x03Pod\x12)\n\x08metadata\x18\x01 \x01(\x0b\x32\x17.k8sschedtpu.ObjectMeta\x12\"\n\x04spec\x18\x02 \x01(\x0b\x32\x14.k8sschedtpu.PodSpec\x12\x1b\n\x13nominated_node_name\x18\x03 \x01(\t\"3\n\x0e\x43ontainerImage\x12\r\n\x05names\x18\x01 \x03(\t\x12\x12\n\nsize_bytes\x18\x02 \x01(\x03\"E\n\x08NodeSpec\x12\"\n\x06taints\x18\x01 \x03(\x0b\x32\x12.k8sschedtpu.Taint\x12\x15\n\runschedulable\x18\x02 \x01(\x08\"\xac\x01\n\nNodeStatus\x12=\n\x0b\x61llocatable\x18\x01 \x03(\x0b\x32(.k8sschedtpu.NodeStatus.AllocatableEntry\x12+\n\x06images\x18\x02 \x03(\x0b\x32\x1b.k8sschedtpu.ContainerImage\x1a\x32\n\x10\x41llocatableEntry\x12\x0b\n\x03key\x18\x01 \x01(\t\x12\r\n\x05value\x18\x02 \x01(\t:\x02\x38\x01\"\x7f\n\x04Node\x12)\n\x08metadata\x18\x01 \x01(\x0b\x32\x17.k8sschedtpu.ObjectMeta\x12#\n\x04spec\x18\x02 \x01(\x0b\x32\x15.k8sschedtpu.NodeSpec\x12\'\n\x06status\x18\x03 \x01(\x0b\x32\x17.k8sschedtpu.NodeStatus\",\n\x08PodGroup\x12\x0c\n\x04name\x18\x01 \x01(\t\x12\x12\n\nmin_member\x18\x02 \x01(\x05\"\x89\x01\n\x0cStorageClass\x12\x0c\n\x04name\x18\x01 \x01(\t\x12\x1b\n\x13volume_binding_mode\x18\x02 \x01(\t\x12\x13\n\x0bprovisioner\x18\x03 \x01(\x08\x12\x39\n\x12\x61llowed_topologies\x18\x04 \x03(\x0b\x32\x1d.k8sschedtpu.NodeSelectorTerm\"\x92\x01\n\x10PersistentVolume\x12\x0c\n\x04name\x18\x01 \x01(\t\x12\x10\n\x08\x63\x61pacity\x18\x02 \x01(\x01\x12\x15\n\rstorage_class\x18\x03 \x01(\t\x12\x34\n\rnode_affinity\x18\x04 \x03(\x0b\x32\x1d.k8sschedtpu.NodeSelectorTerm\x12\x11\n\tclaim_ref\x18\x05 \x01(\t\"u\n\x15PersistentVolumeClaim\x12\x0c\n\x04name\x18\x01 \x01(\t\x12\x11\n\tnamespace\x18\x02 \x01(\t\x12\x15\n\rstorage_class\x18\x03 \x01(\t\x12\x0f\n\x07request\x18\x04 \x01(\x01\x12\x13\n\x0bvolume_name\x18\x05 \x01(\t\"\x81\x01\n\x13PodDisruptionBudget\x12\x0c\n\x04name\x18\x01 \x01(\t\x12\x11\n\tnamespace\x18\x02 \x01(\t\x12,\n\x08selector\x18\x03 \x01(\x0b\x32\x1a.k8sschedtpu.LabelSelector\x12\x1b\n\x13\x64isruptions_allowed\x18\x04 \x01(\x05\"=\n\x08PodEvent\x12\x1d\n\x03pod\x18\x01 \x01(\x0b\x32\x10.k8sschedtpu.Pod\x12\x12\n\nbound_node\x18\x02 \x01(\t\"1\n\x0b\x42indConfirm\x12\x0f\n\x07pod_uid\x18\x01 \x01(\t\x12\x11\n\tnode_name\x18\x02 \x01(\t\"\x8b\x05\n\rUpdateRequest\x12$\n\tnode_adds\x18\x01 \x03(\x0b\x32\x11.k8sschedtpu.Node\x12\'\n\x0cnode_updates\x18\x02 \x03(\x0b\x32\x11.k8sschedtpu.Node\x12\x14\n\x0cnode_deletes\x18\x03 \x03(\t\x12\'\n\x08pod_adds\x18\x04 \x03(\x0b\x32\x15.k8sschedtpu.PodEvent\x12*\n\x0bpod_updates\x18\x05 \x03(\x0b\x32\x15.k8sschedtpu.PodEvent\x12\x13\n\x0bpod_deletes\x18\x06 \x03(\t\x12)\n\npod_groups\x18\x07 \x03(\x0b\x32\x15.k8sschedtpu.PodGroup\x12\x15\n\rbind_failures\x18\x08 \x03(\t\x12\x37\n\x0bpvc_upserts\x18\t \x03(\x0b\x32\".k8sschedtpu.PersistentVolumeClaim\x12\x13\n\x0bpvc_deletes\x18\n \x03(\t\x12\x31\n\npv_upserts\x18\x0b \x03(\x0b\x32\x1d.k8sschedtpu.PersistentVolume\x12\x12\n\npv_deletes\x18\x0c \x03(\t\x12\x38\n\x15storage_class_upserts\x18\r \x03(\x0b\x32\x19.k8sschedtpu.StorageClass\x12\x1d\n\x15storage_class_deletes\x18\x0e \x03(\t\x12\x35\n\x0bpdb_upserts\x18\x0f \x03(\x0b\x32 .k8sschedtpu.PodDisruptionBudget\x12\x13\n\x0bpdb_deletes\x18\x10 \x03(\t\x12/\n\rbind_confirms\x18\x11 \x03(\x0b\x32\x18.k8sschedtpu.BindConfirm\"U\n\x0eUpdateResponse\x12\x0f\n\x07\x62oot_id\x18\x01 \x01(\t\x12\x1d\n\x15\x62ind_confirms_applied\x18\x02 \x01(\x05\x12\x13\n\x0bunconfirmed\x18\x03 \x03(\t\"\x0e\n\x0c\x43ycleRequest\"V\n\x07\x42inding\x12\x0f\n\x07pod_uid\x18\x01 \x01(\t\x12\x10\n\x08pod_name\x18\x02 \x01(\t\x12\x15\n\rpod_namespace\x18\x03 \x01(\t\x12\x11\n\tnode_name\x18\x04 \x01(\t\"0\n\nNomination\x12\x0f\n\x07pod_uid\x18\x01 \x01(\t\x12\x11\n\tnode_name\x18\x02 \x01(\t\"@\n\x08\x45viction\x12\x0f\n\x07pod_uid\x18\x01 \x01(\t\x12\x10\n\x08pod_name\x18\x02 \x01(\t\x12\x11\n\tnode_name\x18\x03 \x01(\t\"\xb0\x01\n\nCycleStats\x12\x11\n\tattempted\x18\x01 \x01(\x05\x12\x11\n\tscheduled\x18\x02 \x01(\x05\x12\x15\n\runschedulable\x18\x03 \x01(\x05\x12\x13\n\x0b\x62ind_errors\x18\x04 \x01(\x05\x12\x12\n\npreemptors\x18\x05 \x01(\x05\x12\x0f\n\x07victims\x18\x06 \x01(\x05\x12\x14\n\x0cgang_dropped\x18\x07 \x01(\x05\x12\x15\n\rcycle_seconds\x18\x08 \x01(\x01\"Y\n\x05\x45vent\x12\x0c\n\x04type\x18\x01 \x01(\t\x12\x0e\n\x06reason\x18\x02 \x01(\t\x12\x0f\n\x07pod_uid\x18\x03 \x01(\t\x12\x10\n\x08pod_name\x18\x04 \x01(\t\x12\x0f\n\x07message\x18\x05 \x01(\t\"\xec\x01\n\rCycleResponse\x12&\n\x08\x62indings\x18\x01 \x03(\x0b\x32\x14.k8sschedtpu.Binding\x12,\n\x0bnominations\x18\x02 \x03(\x0b\x32\x17.k8sschedtpu.Nomination\x12(\n\tevictions\x18\x03 \x03(\x0b\x32\x15.k8sschedtpu.Eviction\x12&\n\x05stats\x18\x04 \x01(\x0b\x32\x17.k8sschedtpu.CycleStats\x12\x0f\n\x07\x62oot_id\x18\x05 \x01(\t\x12\"\n\x06\x65vents\x18\x06 \x03(\x0b\x32\x12.k8sschedtpu.Event\"\x0f\n\rHealthRequest\"=\n\x0eHealthResponse\x12\n\n\x02ok\x18\x01 \x01(\x08\x12\x0e\n\x06status\x18\x02 \x01(\t\x12\x0f\n\x07\x62oot_id\x18\x03 \x01(\t\"\x10\n\x0eMetricsRequest\"*\n\x0fMetricsResponse\x12\x17\n\x0fprometheus_text\x18\x01 \x01(\x0c\"=\n\x0eInspectRequest\x12\x0c\n\x04kind\x18\x01 \x01(\t\x12\x0c\n\x04last\x18\x02 \x01(\x05\x12\x0f\n\x07pod_uid\x18\x03 \x01(\t\":\n\x0fInspectResponse\x12\n\n\x02ok\x18\x01 \x01(\x08\x12\r\n\x05\x65rror\x18\x02 \x01(\t\x12\x0c\n\x04json\x18\x03 \x01(\x0c\"/\n\rSubmitRequest\x12\x1e\n\x04pods\x18\x01 \x03(\x0b\x32\x10.k8sschedtpu.Pod\"Y\n\x0eSubmitResponse\x12\x0f\n\x07\x62oot_id\x18\x01 \x01(\t\x12\x10\n\x08\x61\x63\x63\x65pted\x18\x02 \x01(\x05\x12\x0f\n\x07\x64urable\x18\x03 \x01(\x08\x12\x13\n\x0bqueue_depth\x18\x04 \x01(\x05\"h\n\x10NodeChurnRequest\x12\x1f\n\x04\x61\x64\x64s\x18\x01 \x03(\x0b\x32\x11.k8sschedtpu.Node\x12\"\n\x07updates\x18\x02 \x03(\x0b\x32\x11.k8sschedtpu.Node\x12\x0f\n\x07\x64\x65letes\x18\x03 \x03(\t\"5\n\x11NodeChurnResponse\x12\x0f\n\x07\x62oot_id\x18\x01 \x01(\t\x12\x0f\n\x07\x64urable\x18\x02 \x01(\x08\x32\xec\x03\n\tScheduler\x12\x41\n\x06Update\x12\x1a.k8sschedtpu.UpdateRequest\x1a\x1b.k8sschedtpu.UpdateResponse\x12>\n\x05\x43ycle\x12\x19.k8sschedtpu.CycleRequest\x1a\x1a.k8sschedtpu.CycleResponse\x12\x41\n\x06Health\x12\x1a.k8sschedtpu.HealthRequest\x1a\x1b.k8sschedtpu.HealthResponse\x12\x44\n\x07Metrics\x12\x1b.k8sschedtpu.MetricsRequest\x1a\x1c.k8sschedtpu.MetricsResponse\x12\x44\n\x07Inspect\x12\x1b.k8sschedtpu.InspectRequest\x1a\x1c.k8sschedtpu.InspectResponse\x12\x41\n\x06Submit\x12\x1a.k8sschedtpu.SubmitRequest\x1a\x1b.k8sschedtpu.SubmitResponse\x12J\n\tNodeChurn\x12\x1d.k8sschedtpu.NodeChurnRequest\x1a\x1e.k8sschedtpu.NodeChurnResponseb\x06proto3')

_builder.BuildMessageAndEnumDescriptors(DESCRIPTOR, globals())
_builder.BuildTopDescriptorsAndMessages(DESCRIPTOR, 'scheduler_pb2', globals())
if _descriptor._USE_C_DESCRIPTORS == False:

  DESCRIPTOR._options = None
  _LABELSELECTOR_MATCHLABELSENTRY._options = None
  _LABELSELECTOR_MATCHLABELSENTRY._serialized_options = b'8\001'
  _CONTAINER_REQUESTSENTRY._options = None
  _CONTAINER_REQUESTSENTRY._serialized_options = b'8\001'
  _OBJECTMETA_LABELSENTRY._options = None
  _OBJECTMETA_LABELSENTRY._serialized_options = b'8\001'
  _OBJECTMETA_ANNOTATIONSENTRY._options = None
  _OBJECTMETA_ANNOTATIONSENTRY._serialized_options = b'8\001'
  _PODSPEC_NODESELECTORENTRY._options = None
  _PODSPEC_NODESELECTORENTRY._serialized_options = b'8\001'
  _PODSPEC_OVERHEADENTRY._options = None
  _PODSPEC_OVERHEADENTRY._serialized_options = b'8\001'
  _NODESTATUS_ALLOCATABLEENTRY._options = None
  _NODESTATUS_ALLOCATABLEENTRY._serialized_options = b'8\001'
  _LABELSELECTORREQUIREMENT._serialized_start=32
  _LABELSELECTORREQUIREMENT._serialized_end=105
  _LABELSELECTOR._serialized_start=108
  _LABELSELECTOR._serialized_end=308
  _LABELSELECTOR_MATCHLABELSENTRY._serialized_start=258
  _LABELSELECTOR_MATCHLABELSENTRY._serialized_end=308
  _NODESELECTORTERM._serialized_start=311
  _NODESELECTORTERM._serialized_end=456
  _PREFERREDSCHEDULINGTERM._serialized_start=458
  _PREFERREDSCHEDULINGTERM._serialized_end=550
  _NODEAFFINITY._serialized_start=552
  _NODEAFFINITY._serialized_end=672
  _PODAFFINITYTERM._serialized_start=674
  _PODAFFINITYTERM._serialized_end=785
  _WEIGHTEDPODAFFINITYTERM._serialized_start=787
  _WEIGHTEDPODAFFINITYTERM._serialized_end=872
  _PODAFFINITY._serialized_start=874
  _PODAFFINITY._serialized_end=992
  _AFFINITY._serialized_start=995
  _AFFINITY._serialized_end=1156
  _TOLERATION._serialized_start=1158
  _TOLERATION._serialized_end=1232
  _TAINT._serialized_start=1234
  _TAINT._serialized_end=1285
  _TOPOLOGYSPREADCONSTRAINT._serialized_start=1288
  _TOPOLOGYSPREADCONSTRAINT._serialized_end=1434
  _CONTAINERPORT._serialized_start=1436
  _CONTAINERPORT._serialized_end=1529
  _CONTAINER._serialized_start=1532
  _CONTAINER._serialized_end=1720
  _CONTAINER_REQUESTSENTRY._serialized_start=1673
  _CONTAINER_REQUESTSENTRY._serialized_end=1720
  _OBJECTMETA._serialized_start=1723
  _OBJECTMETA._serialized_end=2024
  _OBJECTMETA_LABELSENTRY._serialized_start=1927
  _OBJECTMETA_LABELSENTRY._serialized_end=1972
  _OBJECTMETA_ANNOTATIONSENTRY._serialized_start=1974
  _OBJECTMETA_ANNOTATIONSENTRY._serialized_end=2024
  _PODSPEC._serialized_start=2027
  _PODSPEC._serialized_end=2615
  _PODSPEC_NODESELECTORENTRY._serialized_start=2515
  _PODSPEC_NODESELECTORENTRY._serialized_end=2566
  _PODSPEC_OVERHEADENTRY._serialized_start=2568
  _PODSPEC_OVERHEADENTRY._serialized_end=2615
  _POD._serialized_start=2617
  _POD._serialized_end=2730
  _CONTAINERIMAGE._serialized_start=2732
  _CONTAINERIMAGE._serialized_end=2783
  _NODESPEC._serialized_start=2785
  _NODESPEC._serialized_end=2854
  _NODESTATUS._serialized_start=2857
  _NODESTATUS._serialized_end=3029
  _NODESTATUS_ALLOCATABLEENTRY._serialized_start=2979
  _NODESTATUS_ALLOCATABLEENTRY._serialized_end=3029
  _NODE._serialized_start=3031
  _NODE._serialized_end=3158
  _PODGROUP._serialized_start=3160
  _PODGROUP._serialized_end=3204
  _STORAGECLASS._serialized_start=3207
  _STORAGECLASS._serialized_end=3344
  _PERSISTENTVOLUME._serialized_start=3347
  _PERSISTENTVOLUME._serialized_end=3493
  _PERSISTENTVOLUMECLAIM._serialized_start=3495
  _PERSISTENTVOLUMECLAIM._serialized_end=3612
  _PODDISRUPTIONBUDGET._serialized_start=3615
  _PODDISRUPTIONBUDGET._serialized_end=3744
  _PODEVENT._serialized_start=3746
  _PODEVENT._serialized_end=3807
  _BINDCONFIRM._serialized_start=3809
  _BINDCONFIRM._serialized_end=3858
  _UPDATEREQUEST._serialized_start=3861
  _UPDATEREQUEST._serialized_end=4512
  _UPDATERESPONSE._serialized_start=4514
  _UPDATERESPONSE._serialized_end=4599
  _CYCLEREQUEST._serialized_start=4601
  _CYCLEREQUEST._serialized_end=4615
  _BINDING._serialized_start=4617
  _BINDING._serialized_end=4703
  _NOMINATION._serialized_start=4705
  _NOMINATION._serialized_end=4753
  _EVICTION._serialized_start=4755
  _EVICTION._serialized_end=4819
  _CYCLESTATS._serialized_start=4822
  _CYCLESTATS._serialized_end=4998
  _EVENT._serialized_start=5000
  _EVENT._serialized_end=5089
  _CYCLERESPONSE._serialized_start=5092
  _CYCLERESPONSE._serialized_end=5328
  _HEALTHREQUEST._serialized_start=5330
  _HEALTHREQUEST._serialized_end=5345
  _HEALTHRESPONSE._serialized_start=5347
  _HEALTHRESPONSE._serialized_end=5408
  _METRICSREQUEST._serialized_start=5410
  _METRICSREQUEST._serialized_end=5426
  _METRICSRESPONSE._serialized_start=5428
  _METRICSRESPONSE._serialized_end=5470
  _INSPECTREQUEST._serialized_start=5472
  _INSPECTREQUEST._serialized_end=5533
  _INSPECTRESPONSE._serialized_start=5535
  _INSPECTRESPONSE._serialized_end=5593
  _SUBMITREQUEST._serialized_start=5595
  _SUBMITREQUEST._serialized_end=5642
  _SUBMITRESPONSE._serialized_start=5644
  _SUBMITRESPONSE._serialized_end=5733
  _NODECHURNREQUEST._serialized_start=5735
  _NODECHURNREQUEST._serialized_end=5839
  _NODECHURNRESPONSE._serialized_start=5841
  _NODECHURNRESPONSE._serialized_end=5894
  _SCHEDULER._serialized_start=5897
  _SCHEDULER._serialized_end=6389
# @@protoc_insertion_point(module_scope)
